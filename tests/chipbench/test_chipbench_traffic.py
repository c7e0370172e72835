"""The traffic generator: deterministic per seed, the same work for
every seed, in another order."""
import json
import sys
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import traffic  # noqa: E402

CHAT = {"arrival": "poisson",
        "prompt_tokens": {"dist": "log_uniform", "min": 32, "max": 128},
        "output_tokens": {"dist": "log_uniform", "min": 64, "max": 512},
        "ramp_s": 8, "tail_s": 30}
OFFLINE = {"arrival": "all_at_once", "n_requests": 300,
           "prompt_tokens": {"dist": "log_uniform", "min": 32, "max": 128},
           "output_tokens": {"dist": "log_uniform", "min": 64, "max": 512}}
CELL = {"rate_per_s": 2.0}
BIG = 2 ** 40 + 17


def _key(plan):
    return [(p.due_s, p.prompt.tolist(), p.max_new_tokens, p.counted)
            for p in plan]


@pytest.mark.parametrize("mix", [CHAT, OFFLINE], ids=["chat", "offline"])
def test_same_seed_same_schedule(mix):
    a = traffic.generate(mix, CELL, BIG, 50, 1000)
    b = traffic.generate(mix, CELL, BIG, 50, 1000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(traffic.generate(mix, CELL, BIG + 1, 50, 1000))


@pytest.mark.parametrize("mix", [CHAT, OFFLINE], ids=["chat", "offline"])
def test_every_seed_gets_the_same_work(mix):
    runs = [traffic.generate(mix, CELL, s, 50, 1000) for s in (1, 7, BIG)]
    for field in ("max_new_tokens", "counted"):
        sets = [Counter(getattr(p, field) for p in plan) for plan in runs]
        assert sets[0] == sets[1] == sets[2]
    lens = [Counter(len(p.prompt) for p in plan) for plan in runs]
    assert lens[0] == lens[1] == lens[2]
    # the gaps of the window, the last one running to its close
    gaps = [sorted(np.round(np.diff([p.due_s for p in plan if p.counted]
                                    + [50.0]), 9)) for plan in runs]
    assert gaps[0] == gaps[1] == gaps[2]


def test_schedule_seed_fixes_the_schedule():
    mix = dict(CHAT, schedule_seed=0)
    a = traffic.generate(mix, CELL, 11, 50, 1000)
    b = traffic.generate(mix, CELL, BIG, 50, 1000)
    assert [(p.due_s, len(p.prompt), p.max_new_tokens) for p in a] == [
        (p.due_s, len(p.prompt), p.max_new_tokens) for p in b]
    assert [p.prompt.tolist() for p in a] != [p.prompt.tolist() for p in b]
    c = traffic.generate(dict(CHAT, schedule_seed=1), CELL, 11, 50, 1000)
    assert [p.due_s for p in a] != [p.due_s for p in c]


def test_open_loop_segments():
    plan = traffic.generate(CHAT, CELL, 3, 50, 1000)
    due = [p.due_s for p in plan]
    assert due == sorted(due)
    counted = [p for p in plan if p.counted]
    assert len(counted) == 100                       # rate x seconds
    assert all(0 <= p.due_s < 50 for p in counted)
    assert sum(p.due_s < 0 for p in plan) == 16      # rate x ramp_s
    assert sum(p.due_s >= 50 for p in plan) == 60    # rate x tail_s
    assert all(not p.counted for p in plan if not 0 <= p.due_s < 50)


def test_lengths_within_the_mix():
    plan = traffic.generate(OFFLINE, CELL, 5, 50, 1000)
    assert len(plan) == 300 and all(p.due_s == 0 for p in plan)
    assert all(32 <= len(p.prompt) <= 128 for p in plan)
    assert all(64 <= p.max_new_tokens <= 512 for p in plan)
    assert all(0 <= t < 1000 for p in plan for t in p.prompt)
    assert list(traffic.prompt_support(OFFLINE)) == list(range(32, 129))


def test_log_uniform_quantiles_span_the_range():
    q = traffic.log_uniform_quantiles(64, 512, 1000)
    assert q.min() == 64 and q.max() == 512
    # log-uniform: as many below the geometric middle as above it
    mid = np.sqrt(64 * 513)
    assert abs((q < mid).sum() - 500) <= 2


def test_exponential_gaps_fill_the_segment():
    g = traffic.exponential_gaps(200, 50.0)
    assert g.sum() == pytest.approx(50.0)
    assert np.all(g > 0)


def test_offline_mix_keeps_the_means_of_its_source():
    """The offline mix's lengths are log-uniform with the means its
    source states, within 2%."""
    mix = json.loads((ROOT / "chipbench" / "traffic" / "offline.json")
                     .read_text())
    for kind, want in mix["source_mean_tokens"].items():
        spec = mix[f"{kind}_tokens"]
        got = traffic.log_uniform_quantiles(spec["min"], spec["max"],
                                            mix["n_requests"]).mean()
        assert abs(got / want - 1) < 0.02, (kind, got, want)

"""Percentiles and window arithmetic on synthetic host timestamps."""
import importlib.util
import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import harness, peaks, stats, traffic  # noqa: E402
from chipbench.architectures import dense  # noqa: E402

with open(ROOT / "chipbench" / "configs" / "stablelm-1.6b-bf16.json") as f:
    STABLELM = json.load(f)
DENSE = harness.architecture(STABLELM)


def _reader(name):
    path = ROOT / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _records(n=40):
    """n counted requests due every 0.5 s: request i waits 10*i ms for
    its prefill to start, gets its first token 5 ms later, and decodes
    10 tokens at (20 + i) ms each. Two uncounted requests ride along."""
    plan, reqs = [], []
    rec = harness.Records([], [], {}, {}, {}, {}, [], 20.0, 0, 0)
    for i in range(n + 2):
        counted = i < n
        due = 0.5 * i if counted else -1.0
        plan.append(traffic.Planned(due, np.arange(8, dtype=np.int32), 11,
                                    counted))
        reqs.append(SimpleNamespace(req_id=i, prompt_len=8,
                                    generated=list(range(11))))
        rec.prefill_start[i] = due + 0.010 * i
        rec.first[i] = rec.prefill_start[i] + 0.005
        rec.done[i] = rec.first[i] + 10 * (0.020 + 0.001 * i)
    rec.plan, rec.requests = plan, reqs
    # 10 prefill steps (one token each), 20 decode steps of 5 tokens
    rec.steps = ([harness.Step(0, 1, 1, 1)] * 10
                 + [harness.Step(1, 2, 0, 5)] * 20)
    rec.decode_calls = 25
    return rec


def _cell(names):
    return harness.Cell("x", 1, STABLELM, {"arrival": "poisson"}, {},
                        [{"name": n} for n in names], [], DENSE)


def test_percentile_is_numpy_linear():
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(list(range(101)), 95) == 95.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_spread_is_iqr_over_median():
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(
        (4.5 - 1.5) / 3.0)


def test_tails_over_counted_requests_only():
    rec = _records()
    out = harness.end_to_end(_cell(["ttft_p90_ms", "tpot_p90_ms"]), rec)
    ttft = [10.0 * i + 5.0 for i in range(40)]          # ms
    tpot = [(10 * (20.0 + i)) / 10 for i in range(40)]  # ms per token
    assert out["ttft_p90_ms"] == pytest.approx(np.percentile(ttft, 90))
    assert out["tpot_p90_ms"] == pytest.approx(np.percentile(tpot, 90))
    assert set(out) == {"ttft_p90_ms", "tpot_p90_ms"}


def test_tokens_per_s_over_the_window():
    rec = _records()
    out = harness.end_to_end(_cell(["tokens_per_s"]), rec)
    assert out["tokens_per_s"] == pytest.approx((10 + 100) / 20.0)


def test_unfinished_counts_requests_never_completed():
    rec = _records()
    cell = _cell([])
    assert harness.unfinished(cell, rec) == 0
    del rec.done[3], rec.done[41]                       # 41 is not counted
    assert harness.unfinished(cell, rec) == 1
    # an unfinished request counts as finishing when the run ended
    rec.closed_s = 1000.0
    out = harness.end_to_end(_cell(["tpot_p90_ms"]), rec)
    tpot = [10.0 * (20.0 + i) / 10 for i in range(40)]
    tpot[3] = 1e3 * (1000.0 - rec.first[3]) / 10
    assert out["tpot_p90_ms"] == pytest.approx(np.percentile(tpot, 90))
    offline = harness.Cell("x", 1, STABLELM, {"arrival": "all_at_once"},
                           {}, [], [], DENSE)
    assert harness.unfinished(offline, rec) == 0


def test_host_clock_readers():
    rec = _records()
    ctx = harness.Context(_cell([]), rec, peaks.peaks_for("TPU v5 lite"),
                          None)
    waits = [10.0 * i for i in range(40)]
    assert _reader("queue_wait_p90_ms")(ctx) == pytest.approx(
        np.percentile(waits, 90))
    ttft = [10.0 * i + 5.0 for i in range(40)]
    assert _reader("ttft_p90_ms.chat")(ctx) == pytest.approx(
        np.percentile(ttft, 90))
    assert _reader("decode_batch_mean")(ctx) == 100 / 25
    rec.compiles = 3
    assert _reader("compiles.chat")(ctx) == 3
    assert _reader("compiles.offline")(ctx) == 3


def test_step_mfu_from_config_shapes():
    rec = _records()
    ctx = harness.Context(_cell([]), rec, peaks.peaks_for("TPU v5 lite"),
                          None)
    work = 42 * (dense.prefill_flops(STABLELM, 8)
                 + dense.decode_flops(STABLELM, 8, 11))
    assert _reader("step_mfu")(ctx) == pytest.approx(
        100 * work / (20.0 * 197e12))


def test_tracer_times_how_long_it_held_the_loop(tmp_path):
    """The profiler runs over its slice of the window; how long its
    start and its stop held the loop is kept, and the directory holds
    the slice's trace."""
    times = iter([1.25, 3.5, 4.0])
    tracer = harness._Tracer(1.0, 2.0, tmp_path / "trace")
    tracer.poll(0.5, lambda: next(times))
    assert tracer.t0 is None
    tracer.poll(1.0, lambda: next(times))     # the start returns at 1.25
    assert (tracer.t0, tracer.start_s) == (1.25, 0.25)
    tracer.poll(3.0, lambda: next(times))
    assert tracer.t1 is None
    tracer.poll(3.5, lambda: next(times))     # the stop returns at 4.0
    assert (tracer.t1, tracer.stop_s) == (3.5, 0.5)
    assert len(list((tmp_path / "trace").rglob("*.xplane.pb"))) == 1

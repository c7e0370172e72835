"""The check that decides ``correct``, at a size a CPU test can hold:
a whole run past the chip check (set-up, warm-up, window, reference),
sound and with the timed path broken underneath; and the control
readings that set a limit."""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import pytest  # noqa: E402

from chipbench import control, harness  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
LIMIT = 0.01  # the tiny model's mean gap: sound runs read < 0.005, the control > 0.02


def _cell(fmt, arrival="poisson", **config):
    cfg = json.loads((DATA / "tiny-int8.json").read_text())
    cfg["precision"] = {"fmt": fmt, "outlier_fraction": 0.01}
    cfg.update(config)
    mix = {"arrival": arrival, "ramp_s": 0.5, "tail_s": 1.0,
           "n_requests": 40,
           "prompt_tokens": {"dist": "log_uniform", "min": 5, "max": 24},
           "output_tokens": {"dist": "log_uniform", "min": 6, "max": 24}}
    settings = {"max_batch": 8, "max_prefill_batch": 2, "buf_len": 48,
                "policy": "slot_count", "rate_per_s": 12.0,
                "check_requests": 4,
                "limits": {"mean_gap": LIMIT}}
    e2e = ([{"name": "ttft_p90_ms", "unit": "ms"},
            {"name": "tpot_p90_ms", "unit": "ms"}]
           if arrival == "poisson" else
           [{"name": "tokens_per_s", "unit": "tokens/s"}])
    return harness.Cell(f"tiny-{fmt}.{arrival}", 1, cfg, mix, settings,
                        e2e + [{"name": "setup_s", "unit": "s"}], [],
                        harness.architecture(cfg))


def _run(cell, seed=2 ** 34 + 5):
    return harness.run(cell, seed, 2.0, False, time.perf_counter(),
                       jax.devices()[0], 1, ROOT / "unused")


@pytest.mark.parametrize("fmt,arrival", [("bfloat16", "poisson"),
                                         ("int8", "all_at_once")])
def test_sound_run_is_correct(fmt, arrival):
    out = _run(_cell(fmt, arrival))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["mean_gap"]["value"] < LIMIT / 2
    assert set(out["metrics"]) == {m["name"] for m in _cell(
        fmt, arrival).end_to_end}


def _alter_tokens(monkeypatch, lanes=1):
    """Every fifth decode step, the token of its first ``lanes`` lanes
    (None: all) is altered where it is produced, in the request and in
    the next step's input."""
    from repro.serving.backend import ExecutedBackend
    real = ExecutedBackend._execute_decode
    calls = [0]

    def altered(self, batch):
        real(self, batch)
        calls[0] += 1
        if calls[0] % 5 == 0:
            for slot, req in list(zip(batch.slots, batch.requests))[:lanes]:
                bad = (req.generated[-1] + 1) % self.cfg.vocab_size
                req.generated[-1] = bad
                self.slot_tokens = self.slot_tokens.at[slot, 0].set(bad)

    monkeypatch.setattr(ExecutedBackend, "_execute_decode", altered)


def _freeze_state(monkeypatch):
    """The decode step returns its cache unchanged."""
    from repro.models.api import Model
    real = Model.decode_step

    def frozen(self, params, tokens, cache):
        logits, _ = real(self, params, tokens, cache)
        return logits, cache

    monkeypatch.setattr(Model, "decode_step", frozen)


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    _alter_tokens(monkeypatch)
    out = _run(_cell("bfloat16"))
    assert not out["correct"]
    assert out["checks"]["mean_gap"]["value"] > LIMIT


def test_step_returning_its_state_unchanged_is_not_correct(monkeypatch):
    _freeze_state(monkeypatch)
    out = _run(_cell("bfloat16"))
    assert not out["correct"]
    assert out["checks"]["mean_gap"]["value"] > LIMIT


@pytest.mark.parametrize("fault", [
    lambda mp: _alter_tokens(mp, lanes=None), _freeze_state],
    ids=["token_altered", "state_unchanged"])
def test_int8_offline_run_with_a_fault_is_not_correct(monkeypatch, fault):
    """An offline cell's path (int8 weights, every request due at
    once, every lane live) fails the check under each fault it can
    have. The check samples requests, so the altered tokens are in
    every lane: a fault in one lane of a full batch is caught only where
    the sample holds that lane's requests."""
    fault(monkeypatch)
    out = _run(_cell("int8", "all_at_once"))
    assert not out["correct"]
    assert out["checks"]["mean_gap"]["value"] > LIMIT


def test_control_reads_above_the_limit():
    """The int8 configuration's control (the program's nf4 path) reads
    above the limit on every seed; the program itself below it."""
    cell = _cell("int8", "all_at_once")
    counter = harness.CompileCounter()
    for seed in (3, 2 ** 36 + 1, 77):
        r = control.one_seed(cell, seed, 2.0, jax.devices()[0], counter)
        assert r["program"]["mean"] < LIMIT < r["control"]["mean"], r


@pytest.mark.parametrize("fmt", ["int8", "nf4"])
def test_control_quantizes_a_layer_at_a_time_as_the_program_does(fmt):
    """The control's parameters, drawn and quantized a layer at a time,
    are those of quantizing the whole tree at once, bit for bit."""
    from repro.models import build_model
    from chipbench import weights
    cfg = json.loads((DATA / "tiny-int8.json").read_text())
    model = build_model(harness.architecture(cfg).model_config(cfg), fmt)
    key = weights.seed_key(2 ** 35 + 11)
    want = model.quantize(weights.make(model, key))
    got = control.lower_params(model, key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert type(got["layers"]["mlp"]["w_up"]).__name__ == {
        "int8": "Int8Weight", "nf4": "NF4Weight"}[fmt]
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert (a.dtype, a.shape) == (b.dtype, b.shape), path
        assert (jax.device_get(a).tobytes()
                == jax.device_get(b).tobytes()), path

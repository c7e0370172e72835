"""Cells, configurations, mixes and metric readers are files found by
name, and BENCHMARK.json keeps to its contract."""
import json
import re
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from chipbench import harness, traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def test_contract_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.fullmatch(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
               for m in BENCH["per_layer"])
    assert layers


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_from_its_files(cell):
    c = harness.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
    s = c.settings
    assert s["max_batch"] >= 1 and s["buf_len"] >= (
        c.mix["prompt_tokens"]["max"] + c.mix["output_tokens"]["max"])
    assert 0 < s["limits"]["mean_gap"] and s["check_requests"] >= 4


def test_chat_cell_reads_its_parts():
    c = harness.load_cell("stablelm-1.6b-bf16.chat")
    assert c.config["num_hidden_layers"] == 24
    assert c.mix["arrival"] == "poisson" and c.settings["rate_per_s"] > 0
    assert {m["name"] for m in c.per_layer} >= {"queue_wait_p90_ms",
                                                "compiles.chat"}


def test_offline_cell_reads_its_parts():
    """The Mistral offline cell reports tokens/s and set-up alone, its
    five readers attach, and the chat cell's lists stay as they were."""
    c = harness.load_cell("mistral-7b-16L-int8.offline")
    assert [m["name"] for m in c.end_to_end] == ["tokens_per_s", "setup_s"]
    assert [m["name"] for m in c.per_layer] == [
        "decode_step_ms.offline", "decode_batch_mean", "step_mfu",
        "quant_matmul_roofline", "compiles.offline"]
    assert all(m["moves"] == "tokens_per_s" for m in c.per_layer)
    assert c.config["architecture_module"] == "dense"
    assert c.config["precision"]["fmt"] == "int8"
    assert c.mix["arrival"] == "all_at_once"
    chat = harness.load_cell("stablelm-1.6b-bf16.chat")
    assert {m["name"] for m in chat.end_to_end} == {
        "tpot_p90_ms", "setup_s"}
    assert not {m["name"] for m in chat.per_layer} & {
        m["name"] for m in c.per_layer}


@pytest.mark.parametrize("name", ["stablelm-1.6b-bf16",
                                  "mistral-7b-16L-int8"])
def test_every_configuration_names_its_architecture_module(name):
    cfg = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                     .read_text())
    assert cfg["architecture_module"] == "dense"
    mc = harness.architecture(cfg).model_config(cfg)
    assert (mc.num_layers, mc.d_model, mc.vocab_size) == (
        cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"])
    chat = harness.load_cell("stablelm-1.6b-bf16.chat")
    assert [m["name"] for m in chat.end_to_end] == [
        "tpot_p90_ms", "setup_s"]
    assert [m["name"] for m in chat.per_layer] == [
        "queue_wait_p90_ms", "prefill_ms", "decode_step_ms.chat",
        "compiles.chat", "decode_host_ms.chat", "cache_insert_ms",
        "engine_wait_p90_ms", "ttft_p90_ms.chat"]


def test_unknown_cell_is_refused():
    with pytest.raises(harness.Refused):
        harness.load_cell("no-such-cell")


@pytest.mark.parametrize("arch", [None, "", "no-such-architecture"])
def test_a_configuration_without_a_known_architecture_is_refused(
        tmp_path, arch):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    path = tmp_path / "chipbench" / "configs" / "stablelm-1.6b-bf16.json"
    cfg = json.loads(path.read_text())
    cfg.pop("architecture_module")
    if arch is not None:
        cfg["architecture_module"] = arch
    path.write_text(json.dumps(cfg))
    with pytest.raises(harness.Refused, match="architecture"):
        harness.load_cell("stablelm-1.6b-bf16.chat", root=tmp_path)


def test_a_new_cell_takes_only_new_files(tmp_path):
    """A configuration, a mix, a cell and a per-layer metric added as
    new files and BENCHMARK.json entries, with no other edit."""
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    cb = tmp_path / "chipbench"
    cfg = json.loads((cb / "configs" / "stablelm-1.6b-bf16.json").read_text())
    cfg.update(name="stablelm-1.6b-8L", num_hidden_layers=8)
    (cb / "configs" / "stablelm-1.6b-8L.json").write_text(json.dumps(cfg))
    (cb / "traffic" / "bursty.json").write_text(json.dumps({
        "arrival": "poisson", "ramp_s": 2, "tail_s": 5,
        "prompt_tokens": {"dist": "log_uniform", "min": 200, "max": 400},
        "output_tokens": {"dist": "log_uniform", "min": 8, "max": 32}}))
    (cb / "cells" / "stablelm-1.6b-8L.bursty.json").write_text(json.dumps({
        "max_batch": 8, "max_prefill_batch": 2, "buf_len": 448,
        "policy": "slot_count", "rate_per_s": 3.0, "check_requests": 4,
        "limits": {"mean_gap": 0.5}}))
    (cb / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return len(ctx.records.steps)\n")
    bench["configs"].append({"name": "stablelm-1.6b-8L", "source": "x",
                             "file": "chipbench/configs/stablelm-1.6b-8L.json",
                             "reduced": ["num_hidden_layers"], "why": "x"})
    bench["workloads"].append({"name": "stablelm-1.6b-8L.bursty",
                               "config": "stablelm-1.6b-8L",
                               "traffic": "bursty", "chips": 1, "why": "x"})
    bench["end_to_end"][0]["workloads"].append("stablelm-1.6b-8L.bursty")
    bench["per_layer"].append({"name": "steps_seen", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "scheduler", "moves": "tpot_p90_ms",
                               "workloads": ["stablelm-1.6b-8L.bursty"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    c = harness.load_cell("stablelm-1.6b-8L.bursty", root=tmp_path)
    assert c.config["num_hidden_layers"] == 8
    assert [m["name"] for m in c.end_to_end] == ["tpot_p90_ms", "setup_s"]
    assert [m["name"] for m in c.per_layer] == ["steps_seen"]
    plan = traffic.generate(c.mix, c.settings, 9, 10, 100)
    assert sum(p.counted for p in plan) == 30
    assert all(200 <= len(p.prompt) <= 400 for p in plan)
    ctx = SimpleNamespace(records=SimpleNamespace(steps=[1, 2, 3]))
    assert harness.read_metric("steps_seen", ctx, root=tmp_path) == 3.0


# A toy architecture: a dense decoder whose configuration file names its
# sizes as GPT-2 does, so that only its own module can read them.
TOY = """
from chipbench.architectures import dense

CALLS = []


def _dense(config):
    return dict(config, num_hidden_layers=config["n_layer"],
                hidden_size=config["n_embd"],
                num_attention_heads=config["n_head"],
                num_key_value_heads=config["n_head"],
                head_dim=config["n_embd"] // config["n_head"],
                intermediate_size=4 * config["n_embd"])


def model_config(config):
    CALLS.append("model_config")
    return dense.model_config(_dense(config))


def logits_at(config, lay, key, rows, targets, first):
    CALLS.append("logits_at")
    return dense.logits_at(_dense(config), lay, key, rows, targets, first)


def prefill_flops(config, prompt_len):
    CALLS.append("prefill_flops")
    return dense.prefill_flops(_dense(config), prompt_len)


def decode_flops(config, prompt_len, generated):
    return dense.decode_flops(_dense(config), prompt_len, generated)
"""


def test_a_new_architecture_takes_only_new_files(tmp_path):
    """A configuration naming an architecture that exists only as a new
    file ``architectures/<name>.py`` loads, builds, runs and is checked
    through that file, with no other edit."""
    import jax
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    cb = tmp_path / "chipbench"
    (cb / "architectures" / "toy.py").write_text(TOY)
    (cb / "configs" / "toy.json").write_text(json.dumps({
        "name": "toy", "source": "x", "architecture_module": "toy",
        "n_layer": 2, "n_embd": 64, "n_head": 4, "vocab_size": 256,
        "rope_theta": 1e4, "attention_bias": False, "rms_norm_eps": 1e-6,
        "precision": {"fmt": "bfloat16"}}))
    (cb / "traffic" / "tiny_offline.json").write_text(json.dumps({
        "arrival": "all_at_once", "n_requests": 24,
        "prompt_tokens": {"dist": "log_uniform", "min": 5, "max": 16},
        "output_tokens": {"dist": "log_uniform", "min": 4, "max": 12}}))
    (cb / "cells" / "toy.tiny_offline.json").write_text(json.dumps({
        "max_batch": 4, "max_prefill_batch": 2, "buf_len": 32,
        "policy": "slot_count", "check_requests": 4,
        "limits": {"mean_gap": 0.01}}))
    bench["configs"].append({"name": "toy", "source": "x",
                             "file": "chipbench/configs/toy.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "toy.tiny_offline", "config": "toy",
                               "traffic": "tiny_offline", "chips": 1,
                               "why": "x"})
    bench["end_to_end"].append({"name": "tokens_per_s", "unit": "tokens/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["toy.tiny_offline"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    c = harness.load_cell("toy.tiny_offline", root=tmp_path)
    assert c.arch.__file__ == str(cb / "architectures" / "toy.py")
    out = harness.run(c, 2 ** 33 + 7, 1.0, False, time.perf_counter(),
                      jax.devices()[0], 1, tmp_path / "out")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert c.arch.CALLS == ["model_config", "logits_at"]
    rec = SimpleNamespace(requests=[SimpleNamespace(
        req_id=0, prompt_len=8, generated=[1, 2, 3])], first={0: 0.1},
        window_s=1.0)
    ctx = SimpleNamespace(cell=c, records=rec,
                          peaks={"bf16_flops_per_s": 1e12})
    assert harness.read_metric("step_mfu", ctx, root=tmp_path) > 0
    assert c.arch.CALLS[-1] == "prefill_flops"

"""Cells, configurations, mixes and metric readers are files found by
name, and BENCHMARK.json keeps to its contract."""
import json
import re
import shutil
import sys
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


def test_unknown_cell_is_refused():
    with pytest.raises(harness.Refused):
        harness.load_cell("no-such-cell")


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
                               "layer": "scheduler", "moves": "ttft_p90_ms",
                               "workloads": ["stablelm-1.6b-8L.bursty"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    c = harness.load_cell("stablelm-1.6b-8L.bursty", root=tmp_path)
    assert c.config["num_hidden_layers"] == 8
    assert [m["name"] for m in c.end_to_end] == ["ttft_p90_ms", "setup_s"]
    assert [m["name"] for m in c.per_layer] == ["steps_seen"]
    plan = traffic.generate(c.mix, c.settings, 9, 10, 100)
    assert sum(p.counted for p in plan) == 30
    assert all(200 <= len(p.prompt) <= 400 for p in plan)
    ctx = SimpleNamespace(records=SimpleNamespace(steps=[1, 2, 3]))
    assert harness.read_metric("steps_seen", ctx, root=tmp_path) == 3.0

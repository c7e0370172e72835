"""A run refuses, exit 2 and no result, off a TPU, on a chip the peaks
table does not know, and without the program beside it."""
import importlib.util
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from chipbench import harness  # noqa: E402

ARGS = ["--workload", "stablelm-1.6b-bf16.chat", "--seed", str(2 ** 33),
        "--seconds", "5", "--trace", "0"]


@pytest.fixture
def run_main():
    spec = importlib.util.spec_from_file_location(
        "chipbench_run_entry", ROOT / "chipbench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


def test_cpu_is_refused(run_main, capsys):
    assert run_main(ARGS) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_unknown_chip_is_refused(run_main, capsys, monkeypatch):
    import jax
    fake = SimpleNamespace(platform="tpu", device_kind="TPU v9 imaginary")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    assert run_main(ARGS) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "peaks table" in out.err


def test_too_few_chips_are_refused(monkeypatch):
    import jax
    fake = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    assert harness.check_device(1) == (fake, 1)
    with pytest.raises(harness.Refused):
        harness.check_device(4)


def test_no_program_is_refused(tmp_path):
    with pytest.raises(harness.Refused):
        harness.import_program(tmp_path)

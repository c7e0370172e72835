"""The trace reduction, on a trace recorded on one TPU v5e: six decode
steps of Mistral-7B widths at 16 layers, int8 through the Pallas
quant_matmul kernel, 64 lanes, buf 640, with the harness's host spans
(``data/mistral-decode.xplane.pb.gz``)."""
import gzip
import importlib.util
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from chipbench import peaks, trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data" / "mistral-decode.xplane.pb.gz"


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(gzip.decompress(DATA.read_bytes()))
    return trace.reduce_profile(pd, window_s=0.3)


def _reader(name):
    path = ROOT / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_busy_is_the_union_of_operations(reduced):
    # the six decode programs run 220.5 ms in all; the small programs
    # between them (argmax, scatters) add a fraction of a millisecond
    assert 0.2200 < reduced.busy_s < 0.2212
    assert reduced.window_s == 0.3


def test_program_time_by_name(reduced):
    assert len(reduced.programs["jit_decode_step"]) == 6
    assert reduced.program_ms("decode") == pytest.approx(36.743, abs=1e-3)
    assert reduced.program_ms("prefill") is None      # none was traced


def test_int8_kernel_calls_and_shapes(reduced):
    calls = reduced.int8_kernel
    assert len(calls) == 6 * 16 * 7          # steps x layers x projections
    shapes = {(m, k, n) for m, k, n, _ in calls}
    assert shapes == {(64, 4096, 4096), (64, 4096, 1024),
                      (64, 4096, 14336), (64, 14336, 4096)}
    assert sum(c[3] for c in calls) == pytest.approx(0.0683, abs=5e-4)


def test_quant_matmul_roofline(reduced):
    ctx = SimpleNamespace(trace=reduced,
                          peaks=peaks.peaks_for("TPU v5 lite"))
    share = _reader("quant_matmul_roofline")(ctx)
    assert 35.0 < share < 45.0
    assert _reader("quant_matmul_roofline")(
        SimpleNamespace(trace=None, peaks=ctx.peaks)) is None


def test_breakdown(reduced):
    ops = reduced.breakdown["device_ops"]
    gaps = reduced.breakdown["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(gaps) <= 10
    assert all(t > 0 for _, t in ops + gaps)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    # the scan over layers contains the other operations: not listed
    assert not any(n.startswith("while") for n, _ in ops)
    assert all(n.startswith("idle in ") for n, _ in gaps)
    assert any("bench.stream_step" in n for n, _ in gaps)


def test_decode_step_readers(reduced):
    ctx = SimpleNamespace(trace=reduced)
    for name in ("decode_step_ms.chat", "decode_step_ms.offline"):
        assert _reader(name)(ctx) == pytest.approx(36.743, abs=1e-3)
        assert _reader(name)(SimpleNamespace(trace=None)) is None
    assert _reader("prefill_ms")(ctx) is None

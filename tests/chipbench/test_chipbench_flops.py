"""Operations and bytes from shapes, at the cells' widths: the dense
architecture's model FLOPs and the int8 kernel's roofline."""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402

from chipbench import flops, peaks  # noqa: E402
from chipbench.architectures import dense  # noqa: E402


def _config(name):
    with open(ROOT / "chipbench" / "configs" / f"{name}.json") as f:
        return json.load(f)


MISTRAL = _config("mistral-7b-16L-int8")
STABLELM = _config("stablelm-1.6b-bf16")
V5E = peaks.PEAKS["TPU v5 lite"]


def test_matmul_params_at_published_widths():
    # 16 layers x (4096*(4096+1024+1024) + 4096*4096 + 3*4096*14336)
    # plus the 4096 x 32768 output head
    assert dense.matmul_params(MISTRAL) == 16 * 218103808 + 134217728
    # stablelm: 24 x (4 * 2048^2 + 3 * 2048 * 5632) + 2048 * 100352
    assert dense.matmul_params(STABLELM) == 24 * 51380224 + 205520896


def test_decode_token_flops():
    per_ctx = 4 * 16 * 32 * 128
    assert dense.decode_token_flops(MISTRAL, 300) == (
        2 * dense.matmul_params(MISTRAL) + per_ctx * 300)
    assert dense.decode_token_flops(MISTRAL, 300) / 1e9 == pytest.approx(
        7.33, abs=0.01)


def test_decode_flops_sums_the_steps():
    want = sum(dense.decode_token_flops(STABLELM, 100 + k)
               for k in range(1, 50))
    assert dense.decode_flops(STABLELM, 100, 50) == want
    assert dense.decode_flops(STABLELM, 100, 1) == 0


def test_prefill_flops_counts_the_head_once():
    p = 128
    layers = dense.matmul_params(STABLELM) - 2048 * 100352
    assert dense.prefill_flops(STABLELM, p) == (
        2 * layers * p + 4 * 24 * 32 * 64 * p * (p + 1) // 2
        + 2 * 2048 * 100352)


@pytest.mark.parametrize("m,bound", [(64, "memory"), (512, "compute")])
def test_int8_kernel_roofline_at_mistral_widths(m, bound):
    ops, nbytes = flops.int8_matmul_cost(m, 4096, 14336)
    assert ops == 2 * m * 4096 * 14336
    assert nbytes == 4096 * 14336 + 4 * 14336 + 2 * m * 4096 + 2 * m * 14336
    t, which = flops.roofline_seconds(ops, nbytes, V5E["bf16_flops_per_s"],
                                      V5E["hbm_bytes_per_s"])
    assert which == bound
    assert t == max(ops / 197e12, nbytes / 819e9)


def test_peaks_table_refuses_an_unknown_chip():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownChip):
        peaks.peaks_for("TPU v9 imaginary")

"""Pallas TPU kernels: quantized matmul with on-the-fly VMEM dequant.

TPU adaptation of bitsandbytes: the packed integer
tile is dequantized *inside VMEM* (VPU work) and fed straight to the MXU
in the compute dtype — no HBM round-trip for the 16-bit weights and no
extra kernel launches, which is precisely the overhead the paper blames
for int8's 2-3x decode-energy regression on the GPU eager path.

Tiling: grid (M/bm, N/bn, K/bk), K innermost; f32 accumulator tile in
VMEM scratch. Default blocks bm=bn=256, bk=512 keep the working set
(int8 tile 128 KiB + dequant tile 256 KiB + acc 256 KiB + x tile 256 KiB)
far under the 16 MiB v5e VMEM while giving the MXU 128-multiple dims.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

import numpy as np

from repro.quant.nf4 import NF4_CODEBOOK

# the codebook as python floats: the kernel looks codes up with a chain
# of selects against these constants (the TPU compiler lowers no gather)
_NF4_LUT = tuple(float(v) for v in np.asarray(NF4_CODEBOOK, np.float32))

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


# ---------------------------------------------------------------------------
# int8: vector-wise absmax — scale applied in the epilogue (scales are
# per-output-column, so they commute with the K-reduction)
# ---------------------------------------------------------------------------
def _int8_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, compute_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = q_ref[...].astype(compute_dtype)            # VMEM dequant (VPU)
    acc_ref[...] += jnp.dot(x_ref[...].astype(compute_dtype), w,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[0, :][None, :]) \
            .astype(o_ref.dtype)


def int8_matmul_pallas(x: jnp.ndarray, codes: jnp.ndarray,
                       scale: jnp.ndarray, *, compute_dtype=jnp.bfloat16,
                       bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                       bk: int = DEFAULT_BK,
                       interpret: bool = False) -> jnp.ndarray:
    """x (M, K) @ dequant(codes (K, N), scale (N,)) -> (M, N)."""
    M, K = x.shape
    N = codes.shape[1]
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(f"shape ({M},{K},{N}) not tileable by "
                         f"({bm},{bk},{bn})")
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_int8_kernel, compute_dtype=compute_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), compute_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, codes, scale.reshape(1, N))


# ---------------------------------------------------------------------------
# nf4: packed 2-per-byte, per-(K-block, column) absmax — dequant must
# happen per K-tile (scales vary along K)
# ---------------------------------------------------------------------------
def _nf4_lookup(codes: jnp.ndarray) -> jnp.ndarray:
    """Codebook values of int32 codes in 0..15, as 15 selects."""
    vals = jnp.full(codes.shape, _NF4_LUT[0], jnp.float32)
    for c in range(1, 16):
        vals = jnp.where(codes == c, _NF4_LUT[c], vals)
    return vals


def _nf4_kernel(xe_ref, xo_ref, p_ref, a_ref, o_ref, acc_ref, *,
                compute_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # packed tile (blocks, block//2, bn): the low nibbles hold the even
    # K-rows of each quant block, the high nibbles the odd ones; the
    # matching activation columns arrive split as xe / xo
    packed = p_ref[...].astype(jnp.int32)
    scale = a_ref[...]                               # (blocks, 1, bn)
    nb, hb, bn = packed.shape

    def dequant(codes):
        w = _nf4_lookup(codes) * scale               # VMEM dequant (VPU)
        return w.reshape(nb * hb, bn).astype(compute_dtype)

    acc_ref[...] += jnp.dot(xe_ref[...].astype(compute_dtype),
                            dequant(packed & 0x0F),
                            preferred_element_type=jnp.float32)
    acc_ref[...] += jnp.dot(xo_ref[...].astype(compute_dtype),
                            dequant(packed >> 4),
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def nf4_matmul_pallas(x: jnp.ndarray, packed: jnp.ndarray,
                      absmax: jnp.ndarray, *, compute_dtype=jnp.bfloat16,
                      bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                      bk: int = DEFAULT_BK,
                      interpret: bool = False) -> jnp.ndarray:
    """x (M, K) @ dequant(packed (K//2, N), absmax (K//block, N))."""
    M, K = x.shape
    N = packed.shape[1]
    if packed.shape[0] * 2 != K:
        raise ValueError("packed rows must be K//2")
    block = K // absmax.shape[0]
    bm, bn = min(bm, M), min(bn, N)
    bk = min(bk, K)
    bk = max(block, (bk // block) * block)           # bk multiple of block
    if M % bm or N % bn or K % bk or block % 2:
        raise ValueError(f"shape ({M},{K},{N}) not tileable by "
                         f"({bm},{bk},{bn}) block={block}")
    grid = (M // bm, N // bn, K // bk)
    nb, hb = bk // block, block // 2
    x_spec = pl.BlockSpec((bm, bk // 2), lambda i, j, k: (i, k))
    return pl.pallas_call(
        functools.partial(_nf4_kernel, compute_dtype=compute_dtype),
        grid=grid,
        in_specs=[
            x_spec,
            x_spec,
            pl.BlockSpec((nb, hb, bn), lambda i, j, k: (k, 0, j)),
            pl.BlockSpec((nb, 1, bn), lambda i, j, k: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), compute_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x[:, 0::2], x[:, 1::2], packed.reshape(K // block, hb, N),
      absmax.reshape(K // block, 1, N))

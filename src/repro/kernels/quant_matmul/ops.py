"""jit'd wrappers: quantized linear ops backed by the Pallas kernels.

These are the entry points :func:`repro.quant.apply.linear_apply` uses
when ``policy.use_pallas_kernels`` is set. The outlier decomposition of
LLM.int8 stays at the XLA level (a thin bf16 matmul added to the kernel
output).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.quant_matmul.kernel import (int8_matmul_pallas,
                                               nf4_matmul_pallas)
from repro.quant.int8 import Int8Weight
from repro.quant.nf4 import NF4Weight

ROW_BLOCK = 256


def _as_2d(x: jnp.ndarray):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _tile(dim: int, *sizes: int) -> int:
    """The largest of ``sizes`` that divides ``dim``, else ``dim``."""
    return next((s for s in sizes if dim % s == 0), dim)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pick_blocks(M: int, K: int, N: int, block: int = 0):
    """(bm, bn, bk, padded M). No row block is taller than
    ``ROW_BLOCK`` (a whole-matrix block at prefill sizes overflows
    VMEM). Up to ``ROW_BLOCK`` rows make one block padded to 8 rows;
    past it the rows split into the fewest blocks that fit, each a
    multiple of 16 rows (the bf16 tile), so padding stays under 16 rows
    per block."""
    bm = _round_up(M, 8)
    if bm > ROW_BLOCK:
        n_blocks = -(-M // ROW_BLOCK)
        bm = _round_up(-(-M // n_blocks), 16)
    bn = _tile(N, 256, 128)
    bk = _tile(K, 512, 256, 128)
    if block:
        bk = max(block, (bk // block) * block)
    return bm, bn, bk, _round_up(M, bm)


def int8_matmul_kernel(x: jnp.ndarray, q: Int8Weight,
                       compute_dtype=jnp.bfloat16,
                       interpret: bool = False) -> jnp.ndarray:
    x2, lead = _as_2d(x)
    M, K = x2.shape
    N = q.codes.shape[1]
    bm, bn, bk, Mp = _pick_blocks(M, K, N)
    xp = jnp.pad(x2, ((0, Mp - M), (0, 0)))
    out = int8_matmul_pallas(xp, q.codes, q.scale,
                             compute_dtype=compute_dtype,
                             bm=bm, bn=bn, bk=bk, interpret=interpret)[:M]
    if q.outlier_idx.shape[0]:
        x_out = jnp.take(x2, q.outlier_idx, axis=-1).astype(compute_dtype)
        out = out + jnp.dot(x_out, q.outlier_w.astype(compute_dtype),
                            preferred_element_type=jnp.float32
                            ).astype(out.dtype)
    return out.reshape(lead + (N,))


def nf4_matmul_kernel(x: jnp.ndarray, q: NF4Weight,
                      compute_dtype=jnp.bfloat16,
                      interpret: bool = False) -> jnp.ndarray:
    x2, lead = _as_2d(x)
    M, K = x2.shape
    N = q.packed.shape[1]
    bm, bn, bk, Mp = _pick_blocks(M, K, N, block=q.block)
    xp = jnp.pad(x2, ((0, Mp - M), (0, 0)))
    out = nf4_matmul_pallas(xp, q.packed, q.absmax,
                            compute_dtype=compute_dtype,
                            bm=bm, bn=bn, bk=bk, interpret=interpret)[:M]
    return out.reshape(lead + (N,))

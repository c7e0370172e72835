"""Pallas TPU paged-attention decode kernel.

vLLM's PagedAttention adapted to TPU: the KV cache
lives in HBM as a pool of fixed-size pages; each sequence owns a chain of
pages recorded in a page table. On GPU, paging exploits gather hardware
inside the kernel; on TPU we express the page lookup as a
*scalar-prefetch* BlockSpec index_map — the page table is prefetched to
SMEM, and each grid step DMAs exactly one page of K/V into VMEM.

Decode shape: one query token per sequence. Grid (B, Kv, n_pages_max),
page innermost, online softmax across pages in VMEM scratch. GQA: all G
query heads of a kv head are processed together — the (G, d) x (d, page)
matmul keeps the MXU busy even at decode.

Padding/validity: slots past ``seq_len`` (and unassigned pages, id < 0)
are masked. Page ids of -1 are clamped to 0 for the DMA (masked anyway).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_size: int, scale: float):
    b, kv, p = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                  # (G, d)
    k = k_ref[0, :, 0, :]                            # (page, d)
    v = v_ref[0, :, 0, :]
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ()))) * scale            # (G, page)
    seq_len = len_ref[b]
    page_id = pt_ref[b, p]
    slot = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    valid = (slot < seq_len) & (page_id >= 0)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...][:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    pexp = jnp.exp(s - m_new[:, None])
    pexp = jnp.where(valid, pexp, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = (l_ref[...][:, 0] * corr
                  + jnp.sum(pexp, axis=1))[:, None]
    acc_ref[...] = acc_ref[...] * corr[:, None] \
        + jnp.dot(pexp.astype(v.dtype), v,
                  preferred_element_type=jnp.float32)
    m_ref[...] = m_new[:, None]

    @pl.when(p == pl.num_programs(2) - 1)
    def _done():
        l = jnp.maximum(l_ref[...][:, 0], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def paged_attention_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, page_table: jnp.ndarray,
                           seq_lens: jnp.ndarray, *,
                           interpret: bool = False) -> jnp.ndarray:
    """Decode attention over a paged KV pool.

    q:          (B, H, d) — one token per sequence
    k_pages:    (n_pages, page_size, Kv, d) HBM pool
    v_pages:    same
    page_table: (B, n_pages_max) int32, -1 padded
    seq_lens:   (B,) int32 valid token counts
    Returns (B, H, d).
    """
    B, H, d = q.shape
    n_pool, page_size, Kv, _ = k_pages.shape
    G = H // Kv
    n_pages_max = page_table.shape[1]
    qf = q.reshape(B, Kv, G, d)

    def q_index(b, kv, p, pt_ref, len_ref):
        return (b, kv, 0, 0)

    def kv_index(b, kv, p, pt_ref, len_ref):
        page = jnp.maximum(pt_ref[b, p], 0)   # clamp -1 (masked in kernel)
        return (page, 0, kv, 0)

    grid = (B, Kv, n_pages_max)
    scale = 1.0 / (d ** 0.5)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=page_size, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, d),
                             lambda b, kv, p, pt, ln: (b, kv, 0, 0)),
                pl.BlockSpec((1, page_size, 1, d), kv_index),
                pl.BlockSpec((1, page_size, 1, d), kv_index),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, G, d), lambda b, kv, p, pt, ln: (b, kv, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Kv, G, d), q.dtype),
        interpret=interpret,
    )(page_table, seq_lens, qf, k_pages, v_pages)
    return out.reshape(B, H, d)

"""Pallas TPU flash attention (prefill) with causal + sliding-window
masking and native GQA.

Schedule: grid (batch*heads, Q blocks, KV blocks), KV innermost; running
max / normalizer / output accumulator live in VMEM scratch across the KV
loop (the canonical TPU flash schedule). GQA is handled in the K/V
BlockSpec index_map — query head h reads kv head h // group — so grouped
K/V are never materialized per-q-head in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30

DEFAULT_BQ = 512
DEFAULT_BKV = 512


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window, bq: int, bkv: int):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                     # (bq, d)
    k = k_ref[0]                                     # (bkv, d)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q.astype(jnp.float32), k.astype(jnp.float32),
        (((1,), (1,)), ((), ()))) * scale            # (bq, bkv)
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kpos = ik * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    allow = jnp.ones((bq, bkv), jnp.bool_)
    if causal:
        allow &= kpos <= qpos
    if window is not None:
        allow &= kpos > qpos - window
    s = jnp.where(allow, s, NEG_INF)

    m_prev = m_ref[...][:, 0]                        # (bq,)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[...][:, 0] * corr + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] \
        + jnp.dot(p.astype(v.dtype), v,
                  preferred_element_type=jnp.float32)
    m_ref[...] = m_new[:, None]
    l_ref[...] = l_new[:, None]

    @pl.when(ik == pl.num_programs(2) - 1)
    def _done():
        l = jnp.maximum(l_ref[...][:, 0], 1e-20)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           *, causal: bool = True, window=None,
                           bq: int = DEFAULT_BQ, bkv: int = DEFAULT_BKV,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B, S, H, d); k/v: (B, T, Kv, d). Returns (B, S, H, d)."""
    B, S, H, d = q.shape
    T, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    bq, bkv = min(bq, S), min(bkv, T)
    if S % bq or T % bkv:
        raise ValueError(f"S={S} T={T} not tileable by ({bq},{bkv})")
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Kv, T, d)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Kv, T, d)

    def kv_index(bh, iq, ik):
        b, h = bh // H, bh % H
        return (b * Kv + h // G, ik, 0)

    grid = (B * H, S // bq, T // bkv)
    scale = 1.0 / (d ** 0.5)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bkv=bkv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bkv, d), kv_index),
            pl.BlockSpec((1, bkv, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),       # running max
            pltpu.VMEM((bq, 1), jnp.float32),       # normalizer
            pltpu.VMEM((bq, d), jnp.float32),       # output accumulator
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, d).transpose(0, 2, 1, 3)

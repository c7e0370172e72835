"""Shared neural-net primitives: norms, RoPE, GQA attention (direct,
chunked-flash, sliding-window), KV caches.

Everything is functional (params-as-pytrees) and shard_map/pjit friendly:
no python-level control flow on traced values, scan over layers happens in
the family modules.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.precision import PrecisionPolicy
from repro.quant.apply import linear_apply

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# norms & embeddings
# ---------------------------------------------------------------------------
def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray,
             eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)
            ).astype(dt)


def embed(tokens: jnp.ndarray, table: jnp.ndarray,
          dtype=jnp.bfloat16) -> jnp.ndarray:
    return jnp.take(table, tokens, axis=0).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)           # (half,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (...,s,half)
    cos = jnp.cos(angles)[..., :, None, :]              # (..., s, 1, half)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def _gqa_scores_einsum(q, k):
    """q: (B,S,Kv,G,hd)  k: (B,T,Kv,hd) -> (B,Kv,G,S,T)."""
    return jnp.einsum("bskgh,btkh->bkgst", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_values_einsum(p, v):
    """p: (B,Kv,G,S,T)  v: (B,T,Kv,hd) -> (B,S,Kv,G,hd)."""
    return jnp.einsum("bkgst,btkh->bskgh", p, v,
                      preferred_element_type=jnp.float32)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              mask: Optional[jnp.ndarray] = None,
              causal: bool = False,
              window: Optional[int] = None,
              q_offset: int | jnp.ndarray = 0) -> jnp.ndarray:
    """Direct GQA attention.

    q: (B, S, H, hd); k/v: (B, T, Kv, hd). H must be a multiple of Kv.
    ``mask``: optional (B, S, T) boolean of *allowed* positions.
    ``q_offset``: absolute position of q[0] (for causal masking against a
    cache).
    Returns (B, S, H, hd).
    """
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    qg = q.reshape(B, S, Kv, G, hd)
    scores = _gqa_scores_einsum(qg, k) / jnp.sqrt(float(hd))
    allow = jnp.ones((S, T), bool)
    qpos = jnp.arange(S) + q_offset
    kpos = jnp.arange(T)
    if causal:
        allow &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        allow &= kpos[None, :] > qpos[:, None] - window
    full = allow[None, None, None]                    # (1,1,1,S,T)
    if mask is not None:
        full = jnp.logical_and(full, mask[:, None, None])
    scores = jnp.where(full, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = _gqa_values_einsum(p.astype(v.dtype), v)
    return out.reshape(B, S, H, hd).astype(q.dtype)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     k_new: jnp.ndarray, v_new: jnp.ndarray,
                     allow: jnp.ndarray) -> jnp.ndarray:
    """One token per row against the cache as it was before this step,
    plus the token's own K/V, under one softmax.

    q: (B, 1, H, hd); k/v: (B, Kv, W, hd), head-major as the cache
    stores them and read where they lie (never written here);
    k_new/v_new: (B, 1, Kv, hd); ``allow``: (B, W) cache slots the token
    may see, excluding the slot it will overwrite.
    The same keys as :func:`attention` over the cache with the token
    written into its slot; only the summation order differs.
    Returns (B, 1, H, hd).
    """
    B, _, H, hd = q.shape
    Kv = k.shape[1]
    qg = q.reshape(B, Kv, H // Kv, hd)
    k_new, v_new = k_new.swapaxes(1, 2), v_new.swapaxes(1, 2)  # (B,Kv,1,hd)

    def scores(kk):                                  # (B, Kv, G, T)
        return jnp.einsum("bkgh,bkth->bkgt", qg, kk,
                          preferred_element_type=jnp.float32) \
            / jnp.sqrt(float(hd))

    def values(p, vv):                               # (B, Kv, G, hd)
        return jnp.einsum("bkgt,bkth->bkgh", p.astype(vv.dtype), vv,
                          preferred_element_type=jnp.float32)

    s_old = jnp.where(allow[:, None, None, :], scores(k), NEG_INF)
    s_new = scores(k_new)                            # (B, Kv, G, 1)
    m = jnp.maximum(jnp.max(s_old, axis=-1, keepdims=True), s_new)
    e_old, e_new = jnp.exp(s_old - m), jnp.exp(s_new - m)
    denom = jnp.sum(e_old, axis=-1, keepdims=True) + e_new
    out = values(e_old / denom, v) + values(e_new / denom, v_new)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool = True,
                      window: Optional[int] = None,
                      chunk_q: int = 512,
                      chunk_k: int = 512) -> jnp.ndarray:
    """Flash-style online-softmax attention in pure jnp (lax.scan tiling).

    Peak memory O(chunk_q * chunk_k) per (batch, head) instead of O(S^2).
    This is the algorithm our Pallas flash kernel implements; XLA lowers
    this scan into a loop so 32k-token prefill fits on-chip memory.
    Shapes as :func:`attention`.
    """
    B, S, H, hd = q.shape
    T, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    if S % chunk_q or T % chunk_k:
        # fall back (small/odd shapes — smoke tests)
        return attention(q, k, v, causal=causal, window=window)
    nq, nk = S // chunk_q, T // chunk_k
    qg = q.reshape(B, nq, chunk_q, Kv, G, hd)
    kc = k.reshape(B, nk, chunk_k, Kv, hd)
    vc = v.reshape(B, nk, chunk_k, Kv, hd)
    scale = 1.0 / jnp.sqrt(float(hd))

    def q_block(qi, q_chunk):
        # q_chunk: (B, chunk_q, Kv, G, hd)
        qpos = qi * chunk_q + jnp.arange(chunk_q)

        def kv_block(carry, inputs):
            m, l, acc = carry
            ki, k_chunk, v_chunk = inputs
            kpos = ki * chunk_k + jnp.arange(chunk_k)
            s = _gqa_scores_einsum(q_chunk, k_chunk) * scale
            allow = jnp.ones((chunk_q, chunk_k), bool)
            if causal:
                allow &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                allow &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(allow[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + _gqa_values_einsum(
                p.astype(v_chunk.dtype), v_chunk).astype(jnp.float32) \
                .reshape(B, chunk_q, Kv, G, hd) \
                .transpose(0, 2, 3, 1, 4)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Kv, G, chunk_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Kv, G, chunk_q), jnp.float32)
        a0 = jnp.zeros((B, Kv, G, chunk_q, hd), jnp.float32)
        ks = jnp.arange(nk)
        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0),
            (ks, kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4)))
        l = jnp.maximum(l, 1e-20)
        out = acc / l[..., None]                       # (B,Kv,G,cq,hd)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, chunk_q, H, hd)

    outs = jax.lax.map(lambda args: q_block(*args),
                       (jnp.arange(nq), qg.transpose(1, 0, 2, 3, 4, 5)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd) \
        .astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache (ring buffer when windowed)
# ---------------------------------------------------------------------------
def init_kv_cache(n_layers: int, batch: int, buf_len: int, n_kv: int,
                  head_dim: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Per-row positions: continuous batching gives every slot (batch row)
    its own sequence, so ``pos`` is (B,) and ``slot_pos`` is (B, W).

    K and V are head-major, (L, B, Kv, W, hd): one layer's slice is what
    :func:`decode_attention` reads, and in this order the TPU compiler
    fuses the read of the slice into the attention dots instead of
    copying it out of the stack first."""
    return {
        "k": jnp.zeros((n_layers, batch, n_kv, buf_len, head_dim), dtype),
        "v": jnp.zeros((n_layers, batch, n_kv, buf_len, head_dim), dtype),
        # absolute position held in each slot (-1 = empty)
        "slot_pos": jnp.full((batch, buf_len), -1, jnp.int32),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def cache_write_decode(cache_layer_k, cache_layer_v, k, v, pos):
    """Write one token's K/V at per-row ring slot pos % W.

    k/v: (B, 1, Kv, hd); pos: (B,) absolute positions."""
    B, W = cache_layer_k.shape[0], cache_layer_k.shape[1]
    slot = jnp.mod(pos, W)
    rows = jnp.arange(B)
    ck = cache_layer_k.at[rows, slot].set(
        k[:, 0].astype(cache_layer_k.dtype))
    cv = cache_layer_v.at[rows, slot].set(
        v[:, 0].astype(cache_layer_v.dtype))
    return ck, cv


def cache_write_tokens(cache: jnp.ndarray, new: jnp.ndarray,
                       slot: jnp.ndarray) -> jnp.ndarray:
    """Write every layer's new token into a stacked head-major cache at
    each row's slot, in place when ``cache`` is donated.

    cache: (L, B, Kv, W, ...); new: (L, B, Kv, ...); slot: (B,).
    A scatter into the cache makes the TPU compiler relayout the whole
    cache. Instead each row reads the 128-slot chunk (or all W slots
    where W is not a multiple of 128) that holds its slot, selects the
    token into it, and writes the chunk back: tile-aligned, and no
    cache-sized copy. The start indices are non-negative by
    construction; JAX's wrap of negative ones would hide from the
    compiler that the start is a multiple of 128, and the writes then
    ran 3.7x slower on a v5e.
    """
    L, B, Kv, W = cache.shape[:4]
    rest = cache.shape[4:]
    chunk = 128 if W % 128 == 0 else W
    at = jnp.arange(chunk).reshape((1, 1, 1, chunk) + (1,) * len(rest))
    tail = (0,) * len(rest)

    def row(b, c):
        start = slot[b] // chunk * chunk
        idx = (0, b, 0, start) + tail
        old = jax.lax.dynamic_slice(c, idx, (L, 1, Kv, chunk) + rest,
                                    allow_negative_indices=False)
        tok = jax.lax.dynamic_slice_in_dim(new, b, 1, axis=1)[:, :, :, None]
        upd = jnp.where(at == slot[b] - start, tok.astype(c.dtype), old)
        return jax.lax.dynamic_update_slice(c, upd, idx,
                                            allow_negative_indices=False)

    return jax.lax.fori_loop(0, B, row, cache)


def decode_attention_mask(slot_pos: jnp.ndarray, pos: jnp.ndarray,
                          window: Optional[int]) -> jnp.ndarray:
    """(B, W) bool — which cache slots each row's current token may see.

    slot_pos: (B, W); pos: (B,)."""
    ok = (slot_pos >= 0) & (slot_pos <= pos[:, None])
    if window is not None:
        ok &= slot_pos > (pos[:, None] - window)
    return ok


def slot_positions_after_prefill(buf_len: int, lengths: jnp.ndarray,
                                 padded_len: int) -> jnp.ndarray:
    """(B, buf) slot_pos after a (possibly padded) prefill.

    Slot i of row b holds absolute position start+i (start>0 only when the
    padded prompt exceeded the buffer); pad slots (>= lengths[b]) are -1.
    """
    idx = jnp.arange(buf_len)[None, :]
    start = max(padded_len - buf_len, 0)
    pos = start + idx
    return jnp.where(pos < lengths[:, None], pos, -1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def gated_mlp(p: Dict[str, Any], x: jnp.ndarray,
              policy: PrecisionPolicy) -> jnp.ndarray:
    g = linear_apply(p["w_gate"], x, policy)
    u = linear_apply(p["w_up"], x, policy)
    return linear_apply(p["w_down"], jax.nn.silu(g) * u, policy)

"""Generic attention-family transformer: dense / MoE / VLM decoders and
the audio encoder-decoder, with scan-over-layers and KV caches.

Three entry modes per layer stack:

* ``forward_seq``  — full-sequence forward (train / prefill). Prefill
  additionally returns the per-layer rotated K/V for the cache.
* ``decode_step``  — one token against a ring-buffer KV cache.

Long sequences (>= ``CHUNKED_ATTN_THRESHOLD``) route through the pure-jnp
flash-style :func:`repro.models.layers.chunked_attention`, so 32k prefill
lowers with O(chunk^2) attention memory.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.precision import PrecisionPolicy
from repro.models import moe as moe_mod
from repro.models.layers import (apply_rope, attention, cache_write_tokens,
                                 chunked_attention, decode_attention,
                                 decode_attention_mask, gated_mlp, rms_norm)
from repro.quant.apply import linear_apply, linear_init

CHUNKED_ATTN_THRESHOLD = 2048


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_attn_params(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    D, hd = cfg.d_model, cfg.head_dim
    p = {
        "wq": linear_init(ks[0], D, cfg.num_heads * hd, dtype),
        "wk": linear_init(ks[1], D, cfg.num_kv_heads * hd, dtype),
        "wv": linear_init(ks[2], D, cfg.num_kv_heads * hd, dtype),
        "wo": linear_init(ks[3], cfg.num_heads * hd, D, dtype),
    }
    if cfg.use_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    return p


def init_mlp_params(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 3)
    D, F = cfg.d_model, cfg.d_ff
    return {
        "w_gate": linear_init(ks[0], D, F, dtype),
        "w_up": linear_init(ks[1], D, F, dtype),
        "w_down": linear_init(ks[2], F, D, dtype),
    }


def init_moe_params(key, cfg: ModelConfig, dtype) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    D, F, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    scale = D ** -0.5
    return {
        "w_router": (jax.random.normal(ks[0], (D, E), jnp.float32)
                     * scale).astype(jnp.float32),
        "experts_gate": (jax.random.normal(ks[1], (E, D, F), jnp.float32)
                         * scale).astype(dtype),
        "experts_up": (jax.random.normal(ks[2], (E, D, F), jnp.float32)
                       * scale).astype(dtype),
        "experts_down": (jax.random.normal(ks[3], (E, F, D), jnp.float32)
                         * F ** -0.5).astype(dtype),
    }


def init_decoder_layer(key, cfg: ModelConfig, dtype,
                       cross_attention: bool = False) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    p = {
        "attn_norm": jnp.ones((cfg.d_model,), dtype),
        "attn": init_attn_params(ks[0], cfg, dtype),
        "mlp_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if cfg.is_moe:
        p["moe"] = init_moe_params(ks[1], cfg, dtype)
    else:
        p["mlp"] = init_mlp_params(ks[1], cfg, dtype)
    if cross_attention:
        p["cross_norm"] = jnp.ones((cfg.d_model,), dtype)
        p["cross"] = init_attn_params(ks[2], cfg, dtype)
    return p


def init_stack(key, cfg: ModelConfig, n_layers: int, dtype,
               cross_attention: bool = False) -> Dict[str, Any]:
    """Stacked (scan-ready) layer params: every leaf gets a leading L dim."""
    keys = jax.random.split(key, n_layers)
    layers = [init_decoder_layer(k, cfg, dtype, cross_attention)
              for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------
def _project_qkv(p: Dict[str, Any], x: jnp.ndarray, cfg: ModelConfig,
                 policy: PrecisionPolicy):
    B, S = x.shape[0], x.shape[1]
    hd = cfg.head_dim
    q = linear_apply(p["wq"], x, policy)
    k = linear_apply(p["wk"], x, policy)
    v = linear_apply(p["wv"], x, policy)
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    return q, k, v


def attn_block_seq(p: Dict[str, Any], x: jnp.ndarray, cfg: ModelConfig,
                   policy: PrecisionPolicy, *, causal: bool = True,
                   window: Optional[int] = None,
                   positions: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Self-attention over a full sequence. Returns (out, k_rot, v)."""
    B, S = x.shape[0], x.shape[1]
    xn = rms_norm(x, p["attn_norm"])
    q, k, v = _project_qkv(p["attn"], xn, cfg, policy)
    if positions is None:
        positions = jnp.arange(S)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if S >= CHUNKED_ATTN_THRESHOLD:
        o = chunked_attention(q, k, v, causal=causal, window=window)
    else:
        o = attention(q, k, v, causal=causal, window=window)
    o = linear_apply(p["attn"]["wo"], o.reshape(B, S, -1), policy)
    return x + o, k, v


def cross_attn_block(p: Dict[str, Any], x: jnp.ndarray,
                     enc_k: jnp.ndarray, enc_v: jnp.ndarray,
                     cfg: ModelConfig, policy: PrecisionPolicy
                     ) -> jnp.ndarray:
    """Cross-attention against precomputed encoder K/V (no rope)."""
    B, S = x.shape[0], x.shape[1]
    xn = rms_norm(x, p["cross_norm"])
    q = linear_apply(p["cross"]["wq"], xn, policy) \
        .reshape(B, S, cfg.num_heads, cfg.head_dim)
    o = attention(q, enc_k, enc_v, causal=False)
    return x + linear_apply(p["cross"]["wo"], o.reshape(B, S, -1), policy)


def ffn_block(p: Dict[str, Any], x: jnp.ndarray, cfg: ModelConfig,
              policy: PrecisionPolicy
              ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    xn = rms_norm(x, p["mlp_norm"])
    if cfg.is_moe:
        B, S, D = xn.shape
        y, aux = moe_mod.moe_ffn(p["moe"], xn.reshape(B * S, D),
                                 top_k=cfg.experts_per_token, policy=policy,
                                 capacity_factor=cfg.moe_capacity_factor)
        return x + y.reshape(B, S, D), aux
    return x + gated_mlp(p["mlp"], xn, policy), {}


def quantize_kv(x: jnp.ndarray):
    """absmax int8 quantization over the head_dim (last axis).

    x: (..., hd) bf16 -> (codes int8 (..., hd), scale f32 (...,)).
    The decode cache's dominant HBM term halves (EXPERIMENTS.md §Perf
    H3); dequantization happens in-register next to the attention dots.
    """
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32)
                               / scale[..., None]), -127, 127) \
        .astype(jnp.int8)
    return codes, scale.astype(jnp.float32)


def dequantize_kv(codes: jnp.ndarray, scale: jnp.ndarray,
                  dtype) -> jnp.ndarray:
    return (codes.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _zero_aux() -> Dict[str, jnp.ndarray]:
    return {"load_balance_loss": jnp.zeros((), jnp.float32),
            "router_z_loss": jnp.zeros((), jnp.float32),
            "dropped_fraction": jnp.zeros((), jnp.float32)}


def decoder_forward_seq(stack: Dict[str, Any], x: jnp.ndarray,
                        cfg: ModelConfig, policy: PrecisionPolicy, *,
                        causal: bool = True,
                        window: Optional[int] = None,
                        collect_kv: bool = False,
                        enc_kv: Optional[Tuple] = None,
                        remat: bool = False):
    """Scan the decoder stack over a full sequence.

    Returns (hidden, kv_stack or None, aux_mean).
    ``enc_kv``: optional (k_stack, v_stack) of per-layer encoder K/V for
    cross-attention — shapes (L, B, S_enc, Kv, hd).
    """
    is_moe = cfg.is_moe
    has_cross = enc_kv is not None

    def layer(carry, inp):
        x, aux = carry
        if has_cross:
            lp, ek, ev = inp
        else:
            lp = inp
        x, k, v = attn_block_seq(lp, x, cfg, policy, causal=causal,
                                 window=window)
        if has_cross:
            x = cross_attn_block(lp, x, ek, ev, cfg, policy)
        x, a = ffn_block(lp, x, cfg, policy)
        if is_moe:
            aux = {key: aux[key] + a[key] for key in aux}
        ys = (k, v) if collect_kv else None
        return (x, aux), ys

    if remat:
        layer = jax.checkpoint(layer)
    xs = (stack, enc_kv[0], enc_kv[1]) if has_cross else stack
    (x, aux), kv = jax.lax.scan(layer, (x, _zero_aux()), xs)
    n = cfg.num_layers
    aux = {k: v / n for k, v in aux.items()}
    return x, kv, aux


def decoder_decode_step(stack: Dict[str, Any], x: jnp.ndarray,
                        cache: Dict[str, Any], cfg: ModelConfig,
                        policy: PrecisionPolicy, *,
                        window: Optional[int] = None,
                        enc_kv: Optional[Tuple] = None):
    """One-token decode. x: (B, 1, D). cache: see layers.init_kv_cache
    (head-major K/V (L, B, Kv, W, hd), per-row pos (B,) / slot_pos
    (B, W)).

    Each layer attends over its cache slice as it was before this step
    (the slot being overwritten masked out) plus the token's own K/V;
    the scan emits only the new K/V, written into the cache after it
    by :func:`cache_write_tokens` (in place when the cache is donated).
    Returns (hidden (B,1,D), new_cache).
    """
    pos = cache["pos"]                                         # (B,)
    slot_pos = cache["slot_pos"]                               # (B, W)
    W = cache["k"].shape[3]
    B = x.shape[0]
    slot = jnp.mod(pos, W)
    allow = (decode_attention_mask(slot_pos, pos, window)
             & (jnp.arange(W)[None, :] != slot[:, None]))      # (B, W)
    has_cross = enc_kv is not None
    quant = "k_scale" in cache                                 # int8 KV
    keys = ("k", "v", "k_scale", "v_scale") if quant else ("k", "v")
    adt = policy.activation_dtype

    def layer(x, inp):
        lp, kv, enc = inp
        xn = rms_norm(x, lp["attn_norm"])
        q, k, v = _project_qkv(lp["attn"], xn, cfg, policy)
        pos1 = pos[:, None]                                    # (B, 1)
        q = apply_rope(q, pos1, cfg.rope_theta)
        k = apply_rope(k, pos1, cfg.rope_theta)
        if quant:
            ck, cv, ks, vs = kv
            (kq, ksc), (vq, vsc) = quantize_kv(k), quantize_kv(v)
            kf, vf = dequantize_kv(ck, ks, adt), dequantize_kv(cv, vs, adt)
            k_tok = dequantize_kv(kq, ksc, adt)
            v_tok = dequantize_kv(vq, vsc, adt)
            new = (kq, vq, ksc, vsc)
        else:
            kf, vf = kv
            k_tok, v_tok = k.astype(kf.dtype), v.astype(vf.dtype)
            new = (k_tok, v_tok)
        o = decode_attention(q, kf, vf, k_tok, v_tok, allow)
        x = x + linear_apply(lp["attn"]["wo"],
                             o.reshape(B, 1, -1), policy)
        if has_cross:
            x = cross_attn_block(lp, x, *enc, cfg, policy)
        x, _ = ffn_block(lp, x, cfg, policy)
        return x, tuple(a[:, 0] for a in new)

    xs = (stack, tuple(cache[key] for key in keys),
          enc_kv if has_cross else ())
    x, out = jax.lax.scan(layer, x, xs)
    new_cache = dict(cache, slot_pos=slot_pos.at[jnp.arange(B), slot]
                     .set(pos), pos=pos + 1)
    for key, tok in zip(keys, out):
        new_cache[key] = cache_write_tokens(cache[key], tok, slot)
    return x, new_cache

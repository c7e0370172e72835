"""Unified model facade over the six architecture families.

A :class:`Model` bundles (config, precision policy) and exposes pure
functions suitable for jit/pjit:

* ``init(key)``                          -> params pytree
* ``forward_train(params, batch)``       -> (hidden, aux)   [full seq]
* ``prefill(params, batch, buf_len)``    -> (last_logits, cache)
* ``decode_step(params, tokens, cache)`` -> (logits, cache)
* ``logits(params, hidden)``             -> LM-head projection
* ``input_specs(shape)``                 -> ShapeDtypeStructs for dry-run

Families: dense / moe / vlm share the decoder stack; audio adds an
encoder + cross-attention; ssm is the Mamba2 stack; hybrid is Mamba2 +
shared attention. VLM patch embeddings and audio frame embeddings are
stubbed inputs per the assignment carve-out.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.precision import PrecisionPolicy, make_policy
from repro.models import hybrid as hybrid_mod
from repro.models import ssm as ssm_mod
from repro.models import transformer as tfm
from repro.models.layers import (embed, init_kv_cache, rms_norm,
                                 slot_positions_after_prefill)
from repro.quant.apply import linear_apply, linear_init, quantize_params


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    policy: PrecisionPolicy
    # sliding-window override (the long_500k SWA-variant for full-attention
    # archs). None = use cfg.sliding_window.
    window_override: Optional[int] = None
    # int8 KV cache: absmax-per-(token, head)
    # quantized K/V halves the decode phase's dominant HBM term. Applies
    # to the transformer-family caches (dense/moe/vlm/audio).
    kv_quant: bool = False

    # ------------------------------------------------------------------
    @property
    def window(self) -> Optional[int]:
        return (self.window_override if self.window_override is not None
                else self.cfg.sliding_window)

    @property
    def adt(self):
        return self.policy.activation_dtype

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = self.policy.param_dtype
        k_embed, k_layers, k_head, k_extra = jax.random.split(key, 4)
        params: Dict[str, Any] = {
            "embed": (jax.random.normal(
                k_embed, (cfg.vocab_size, cfg.d_model), jnp.float32)
                * 0.02).astype(dtype),
            "final_norm": jnp.ones((cfg.d_model,), dtype),
            "lm_head": linear_init(k_head, cfg.d_model, cfg.vocab_size,
                                   dtype),
        }
        if cfg.family in ("dense", "moe", "vlm"):
            params["layers"] = tfm.init_stack(k_layers, cfg,
                                              cfg.num_layers, dtype)
        elif cfg.family == "audio":
            params["enc_layers"] = tfm.init_stack(k_extra, cfg,
                                                  cfg.enc_layers, dtype)
            params["enc_norm"] = jnp.ones((cfg.d_model,), dtype)
            params["layers"] = tfm.init_stack(k_layers, cfg, cfg.num_layers,
                                              dtype, cross_attention=True)
        elif cfg.family == "ssm":
            keys = jax.random.split(k_layers, cfg.num_layers)
            layers = [ssm_mod.init_mamba_layer(k, cfg, dtype) for k in keys]
            params["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs),
                                            *layers)
        elif cfg.family == "hybrid":
            params.update(hybrid_mod.init_params(k_layers, cfg, dtype))
        else:
            raise ValueError(cfg.family)
        return params

    def quantize(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """Post-training quantization under the model's policy."""
        return quantize_params(params, self.policy)

    # ------------------------------------------------------------------
    # embedding assembly per family
    # ------------------------------------------------------------------
    def _embed_inputs(self, params, batch: Dict[str, jnp.ndarray]):
        x = embed(batch["tokens"], params["embed"], self.adt)
        if self.cfg.family == "vlm" and "patches" in batch:
            x = jnp.concatenate([batch["patches"].astype(self.adt), x],
                                axis=1)
        return x

    def _encode_audio(self, params, frames: jnp.ndarray):
        """Bidirectional encoder over stub frame embeddings."""
        h, _, _ = tfm.decoder_forward_seq(
            params["enc_layers"], frames.astype(self.adt), self.cfg,
            self.policy, causal=False, collect_kv=False)
        return rms_norm(h, params["enc_norm"])

    def _cross_kv(self, params, enc_out: jnp.ndarray):
        """Per-decoder-layer cross-attention K/V from encoder output."""
        cfg = self.cfg

        def one_layer(lp):
            B, S = enc_out.shape[0], enc_out.shape[1]
            k = linear_apply(lp["cross"]["wk"], enc_out, self.policy) \
                .reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
            v = linear_apply(lp["cross"]["wv"], enc_out, self.policy) \
                .reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
            return k, v

        ks, vs = jax.lax.map(one_layer, params["layers"])
        return ks, vs

    # ------------------------------------------------------------------
    # full-sequence forward (train / eval)
    # ------------------------------------------------------------------
    def forward_train(self, params, batch: Dict[str, jnp.ndarray],
                      remat: bool = False):
        """Returns (hidden (B, S_total, D), aux dict)."""
        cfg = self.cfg
        if cfg.family in ("dense", "moe", "vlm"):
            x = self._embed_inputs(params, batch)
            h, _, aux = tfm.decoder_forward_seq(
                params["layers"], x, cfg, self.policy, causal=True,
                window=self.window, remat=remat)
        elif cfg.family == "audio":
            enc_out = self._encode_audio(params, batch["frames"])
            enc_kv = self._cross_kv(params, enc_out)
            x = embed(batch["tokens"], params["embed"], self.adt)
            h, _, aux = tfm.decoder_forward_seq(
                params["layers"], x, cfg, self.policy, causal=True,
                window=self.window, enc_kv=enc_kv, remat=remat)
        elif cfg.family == "ssm":
            x = embed(batch["tokens"], params["embed"], self.adt)
            h = self._ssm_forward(params, x)
            aux = {}
        elif cfg.family == "hybrid":
            x = embed(batch["tokens"], params["embed"], self.adt)
            h, _ = hybrid_mod.forward_seq(params, x, cfg, self.policy)
            aux = {}
        else:
            raise ValueError(cfg.family)
        return rms_norm(h, params["final_norm"]), aux

    def _ssm_forward(self, params, x, collect_cache: bool = False,
                     lengths: Optional[jnp.ndarray] = None):
        cfg = self.cfg
        dims = ssm_mod.ssm_dims(cfg)
        B, S = x.shape[0], x.shape[1]
        h0 = jnp.zeros((B, dims["nheads"], dims["headdim"], dims["dstate"]),
                       jnp.float32)
        seq_mask = None
        if lengths is not None:
            seq_mask = (jnp.arange(S)[None, :]
                        < lengths[:, None]).astype(jnp.float32)

        def layer(x, lp):
            x, h, tail = ssm_mod.mamba_block(lp, x, cfg, self.policy, h0,
                                             seq_mask=seq_mask)
            return x, (h, tail)

        x, (hs, tails) = jax.lax.scan(layer, x, params["layers"])
        if collect_cache:
            return x, {"ssm_state": hs, "conv": tails,
                       "pos": jnp.zeros((), jnp.int32)}
        return x

    # ------------------------------------------------------------------
    # logits
    # ------------------------------------------------------------------
    def logits(self, params, hidden: jnp.ndarray) -> jnp.ndarray:
        return linear_apply(params["lm_head"], hidden, self.policy) \
            .astype(jnp.float32)

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------
    def prefill(self, params, batch: Dict[str, jnp.ndarray],
                buf_len: Optional[int] = None,
                lengths: Optional[jnp.ndarray] = None):
        """Forward over the prompt, build the decode cache.

        ``lengths``: (B,) true prompt lengths when the batch is
        right-padded (static batching, §4); defaults to the full width.
        Returns (last_token_logits (B, V), cache) with logits taken at
        each row's final *real* token.
        """
        cfg = self.cfg
        if cfg.family in ("dense", "moe", "vlm"):
            x = self._embed_inputs(params, batch)
            B, S = x.shape[0], x.shape[1]
            lengths = self._lengths(lengths, B, S, batch)
            # vlm: the patch prefix counts toward every row's length
            lengths = lengths + (S - batch["tokens"].shape[1])
            buf = self._buf_len(S, buf_len)
            h, kv, _ = tfm.decoder_forward_seq(
                params["layers"], x, cfg, self.policy, causal=True,
                window=self.window, collect_kv=True)
            cache = self._kv_cache_from_prefill(kv, B, S, buf, lengths)
        elif cfg.family == "audio":
            enc_out = self._encode_audio(params, batch["frames"])
            enc_kv = self._cross_kv(params, enc_out)
            x = embed(batch["tokens"], params["embed"], self.adt)
            B, S = x.shape[0], x.shape[1]
            lengths = self._lengths(lengths, B, S, batch)
            buf = self._buf_len(S, buf_len)
            h, kv, _ = tfm.decoder_forward_seq(
                params["layers"], x, cfg, self.policy, causal=True,
                window=self.window, enc_kv=enc_kv, collect_kv=True)
            cache = self._kv_cache_from_prefill(kv, B, S, buf, lengths)
            cache["enc_k"], cache["enc_v"] = enc_kv
        elif cfg.family == "ssm":
            x = embed(batch["tokens"], params["embed"], self.adt)
            B, S = x.shape[0], x.shape[1]
            lengths = self._lengths(lengths, B, S, batch)
            h, cache = self._ssm_forward(params, x, collect_cache=True,
                                         lengths=lengths)
            cache["pos"] = lengths.astype(jnp.int32)
        elif cfg.family == "hybrid":
            x = embed(batch["tokens"], params["embed"], self.adt)
            B, S = x.shape[0], x.shape[1]
            lengths = self._lengths(lengths, B, S, batch)
            h, cache = hybrid_mod.forward_seq(
                params, x, cfg, self.policy, collect_cache=True,
                buf_len=self._buf_len(S, buf_len), lengths=lengths)
        else:
            raise ValueError(cfg.family)
        h = rms_norm(h, params["final_norm"])
        last = jnp.take_along_axis(
            h, (lengths - 1)[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]
        return self.logits(params, last), cache

    @staticmethod
    def _lengths(lengths, B, S, batch):
        if lengths is not None:
            return jnp.asarray(lengths, jnp.int32)
        return jnp.full((B,), batch["tokens"].shape[1], jnp.int32)

    def _buf_len(self, S: int, buf_len: Optional[int]) -> int:
        if self.window is not None:
            return min(buf_len or (S + 32), self.window)
        return buf_len or (S + 32)

    def _kv_cache_from_prefill(self, kv, B, S, buf, lengths):
        k, v = kv                              # (L, B, S, Kv, hd)
        W = buf
        if S >= W:
            k, v = k[:, :, S - W:], v[:, :, S - W:]
        else:
            pad = [(0, 0), (0, 0), (0, W - S), (0, 0), (0, 0)]
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        cache = {
            "slot_pos": slot_positions_after_prefill(W, lengths, S),
            "pos": lengths.astype(jnp.int32),
        }
        if self.kv_quant:
            from repro.models.transformer import quantize_kv
            (cache["k"], cache["k_scale"]) = quantize_kv(k)
            (cache["v"], cache["v_scale"]) = quantize_kv(v)
        else:
            cache["k"], cache["v"] = k, v
        return cache

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def decode_step(self, params, tokens: jnp.ndarray, cache):
        """tokens: (B, 1) int32. Returns (logits (B, V), new cache)."""
        cfg = self.cfg
        x = embed(tokens, params["embed"], self.adt)
        if cfg.family in ("dense", "moe", "vlm"):
            h, cache = tfm.decoder_decode_step(
                params["layers"], x, cache, cfg, self.policy,
                window=self.window)
        elif cfg.family == "audio":
            enc_kv = (cache["enc_k"], cache["enc_v"])
            keys = ["k", "v", "slot_pos", "pos"]
            if "k_scale" in cache:
                keys += ["k_scale", "v_scale"]
            sub = {k: cache[k] for k in keys}
            h, sub = tfm.decoder_decode_step(
                params["layers"], x, sub, cfg, self.policy,
                window=self.window, enc_kv=enc_kv)
            cache = dict(cache, **sub)
        elif cfg.family == "ssm":
            h2d, cache = self._ssm_decode(params, x[:, 0, :], cache)
            h = h2d[:, None, :]
        elif cfg.family == "hybrid":
            h, cache = hybrid_mod.decode_step(params, x, cache, cfg,
                                              self.policy)
        else:
            raise ValueError(cfg.family)
        h = rms_norm(h, params["final_norm"])
        return self.logits(params, h[:, -1]), cache

    def _ssm_decode(self, params, x2d, cache):
        cfg = self.cfg

        def layer(x, inp):
            lp, h, conv_c = inp
            x, h_new, conv_new = ssm_mod.mamba_block_decode(
                lp, x, cfg, self.policy, h, conv_c)
            return x, (h_new, conv_new)

        x2d, (hs, convs) = jax.lax.scan(
            layer, x2d, (params["layers"], cache["ssm_state"],
                         cache["conv"]))
        return x2d, dict(cache, ssm_state=hs, conv=convs,
                         pos=cache["pos"] + 1)

    # ------------------------------------------------------------------
    # empty decode cache (serving engine: decode-only entry)
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, buf_len: int, enc_len: int = 0):
        cfg = self.cfg
        adt = self.adt
        W = min(buf_len, self.window) if self.window else buf_len
        if cfg.family in ("dense", "moe", "vlm", "audio"):
            c = init_kv_cache(cfg.num_layers, batch, W,
                              cfg.num_kv_heads, cfg.head_dim, adt)
            if self.kv_quant:
                c["k"] = jnp.zeros(c["k"].shape, jnp.int8)
                c["v"] = jnp.zeros(c["v"].shape, jnp.int8)
                c["k_scale"] = jnp.zeros(c["k"].shape[:-1], jnp.float32)
                c["v_scale"] = jnp.zeros(c["v"].shape[:-1], jnp.float32)
            if cfg.family == "audio":
                c["enc_k"] = jnp.zeros((cfg.num_layers, batch, enc_len,
                                        cfg.num_kv_heads, cfg.head_dim),
                                       adt)
                c["enc_v"] = jnp.zeros_like(c["enc_k"])
            return c
        dims = ssm_mod.ssm_dims(cfg)
        ssm_cache = {
            "ssm_state": jnp.zeros((cfg.num_layers, batch, dims["nheads"],
                                    dims["headdim"], dims["dstate"]),
                                   jnp.float32),
            "conv": jnp.zeros((cfg.num_layers, batch,
                               cfg.ssm_conv_width - 1,
                               dims["conv_channels"]), adt),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
        if cfg.family == "ssm":
            return ssm_cache
        # hybrid
        sites = hybrid_mod.n_attn_sites(cfg)
        ssm_cache.update({
            "shared_k": jnp.zeros((sites, batch, W, cfg.num_kv_heads,
                                   cfg.head_dim), adt),
            "shared_v": jnp.zeros((sites, batch, W, cfg.num_kv_heads,
                                   cfg.head_dim), adt),
            "slot_pos": jnp.full((batch, W), -1, jnp.int32),
        })
        return ssm_cache

    # ------------------------------------------------------------------
    # dry-run input specs
    # ------------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for every model input."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
        specs: Dict[str, Any] = {"tokens": tok}
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        if cfg.family == "vlm":
            specs["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.num_patches, cfg.d_model), self.adt)
        if cfg.family == "audio":
            specs["frames"] = jax.ShapeDtypeStruct(
                (B, S // cfg.enc_frames_ratio, cfg.d_model), self.adt)
        return specs


def build_model(cfg: ModelConfig, fmt: str = "bfloat16",
                window_override: Optional[int] = None,
                use_pallas_kernels: bool = False,
                kv_quant: bool = False) -> Model:
    policy = make_policy(fmt, use_pallas_kernels=use_pallas_kernels)
    return Model(cfg=cfg, policy=policy, window_override=window_override,
                 kv_quant=kv_quant)

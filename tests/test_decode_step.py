"""The decode step reads each layer's cache where it lies and writes the
new token after the layer scan. It must compute what writing the token
first and attending over the whole buffer computes: the same keys under
one softmax, only the float summation order differs. Checked on the
CPU, in float32, against that write-then-attend step written out here,
for every transformer-family cache: plain, sliding-window ring, int8 KV
and audio cross-attention, over enough steps to wrap the ring with
lanes of unequal length."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import batch_for
from repro.configs import get_config
from repro.models import build_model
from repro.models import transformer as tfm
from repro.models.layers import (apply_rope, attention, cache_write_decode,
                                 cache_write_tokens, decode_attention_mask,
                                 embed, rms_norm)
from repro.quant.apply import linear_apply

B, PROMPT = 3, 9
LENGTHS = np.array([9, 4, 6], np.int32)       # unequal lanes, right-padded


def _reference_layers(stack, x, cache, cfg, policy, window, enc_kv,
                      tok_q):
    """Write the token into its ring slot, then attend over the whole
    buffer, on each layer's cache turned sequence-major.

    With int8 K/V the token's codes and scales are ``tok_q``, as the
    program wrote them, (L, B, Kv, ...): after the two summation orders
    an entry within float error of a rounding tie can take codes one
    step apart, which moves the logits by ~1e-4. The reference's own
    codes are returned beside the cache for that check."""
    pos, W = cache["pos"], cache["k"].shape[3]
    rows, slot = jnp.arange(B), jnp.mod(pos, W)
    slot_pos = cache["slot_pos"].at[rows, slot].set(pos)
    allow = decode_attention_mask(slot_pos, pos, window)
    quant = "k_scale" in cache
    adt = policy.activation_dtype

    def layer(x, inp):
        lp, kv, enc, tq = inp
        ck, cv, *scales = (a.swapaxes(1, 2) for a in kv)   # (B, W, Kv...)
        xn = rms_norm(x, lp["attn_norm"])
        q, k, v = tfm._project_qkv(lp["attn"], xn, cfg, policy)
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
        if quant:
            own = (tfm.quantize_kv(k)[0][:, 0], tfm.quantize_kv(v)[0][:, 0])
            kq, vq, ksc, vsc = (a[:, None] for a in tq)
            ck, cv = cache_write_decode(ck, cv, kq, vq, pos)
            ks = scales[0].at[rows, slot].set(ksc[:, 0])
            vs = scales[1].at[rows, slot].set(vsc[:, 0])
            kf, vf = tfm.dequantize_kv(ck, ks, adt), tfm.dequantize_kv(
                cv, vs, adt)
            out = (ck, cv, ks, vs)
        else:
            ck, cv = cache_write_decode(ck, cv, k, v, pos)
            kf, vf = ck, cv
            out, own = (ck, cv), ()
        o = attention(q, kf, vf, mask=allow[:, None, :])
        x = x + linear_apply(lp["attn"]["wo"], o.reshape(B, 1, -1), policy)
        if enc:
            x = tfm.cross_attn_block(lp, x, *enc, cfg, policy)
        x, _ = tfm.ffn_block(lp, x, cfg, policy)
        return x, (tuple(a.swapaxes(1, 2) for a in out), own)

    keys = ("k", "v", "k_scale", "v_scale") if quant else ("k", "v")
    x, (out, own) = jax.lax.scan(
        layer, x, (stack, tuple(cache[k] for k in keys), enc_kv or (),
                   tok_q))
    return x, dict(cache, slot_pos=slot_pos, pos=pos + 1,
                   **dict(zip(keys, out))), own


def _reference_step(m, params, tokens, cache, tok_q):
    x = embed(tokens, params["embed"], m.adt)
    enc_kv = ((cache["enc_k"], cache["enc_v"])
              if m.cfg.family == "audio" else None)
    h, cache, own = _reference_layers(params["layers"], x, cache, m.cfg,
                                      m.policy, m.window, enc_kv, tok_q)
    h = rms_norm(h, params["final_norm"])
    return m.logits(params, h[:, -1]), cache, own


def _written(cache, slot):
    """The int8 codes and scales at each row's ``slot``, every layer
    and head: (L, B, Kv, ...)."""
    rows = jnp.arange(B)
    return tuple(jnp.moveaxis(cache[key][:, rows, :, slot], 0, 1)
                 for key in ("k", "v", "k_scale", "v_scale"))


def _gqa4(arch):
    """A reduced config with Mistral's grouping: 4 query heads per KV
    head, head_dim 128."""
    return dataclasses.replace(get_config(arch).reduced(), num_heads=8,
                               num_kv_heads=2, head_dim=128)


def _config(c):
    return (_gqa4(c["arch"]) if c.get("gqa4")
            else get_config(c["arch"]).reduced())


CASES = {
    # 16-slot buffer: the ring wraps after 7-12 steps
    "plain": dict(arch="stablelm-1.6b", buf=16),
    # window 12 = the ring: the overwritten slot leaves the window
    "sliding_window": dict(arch="h2o-danube-3-4b", buf=32, window=12),
    "kv_quant": dict(arch="stablelm-1.6b", buf=16, kv_quant=True),
    "audio_cross": dict(arch="seamless-m4t-large-v2", buf=16),
    # G = 4, hd 128: the grouping whose dots the TPU runs on the MXU
    "gqa4": dict(arch="minitron-8b", buf=16, gqa4=True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_decode_step_matches_write_then_attend(case):
    c = CASES[case]
    cfg = _config(c)
    m = build_model(cfg, fmt="float32", window_override=c.get("window"),
                    kv_quant=c.get("kv_quant", False))
    params = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0,
                              cfg.vocab_size)
    logits, cache = m.prefill(params, batch_for(cfg, toks),
                              buf_len=c["buf"], lengths=LENGTHS)
    W = cache["k"].shape[3]
    ref_cache = cache
    step, ref_step = jax.jit(m.decode_step), jax.jit(
        lambda p, t, c, q: _reference_step(m, p, t, c, q))
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for _ in range(W + 4):                      # every lane wraps
        slot = cache["pos"] % W
        logits, cache = step(params, tok, cache)
        tok_q = _written(cache, slot) if c.get("kv_quant") else ()
        ref_logits, ref_cache, own = ref_step(params, tok, ref_cache, tok_q)
        np.testing.assert_allclose(logits, ref_logits, rtol=1e-5,
                                   atol=1e-5)
        for mine, theirs in zip(own, tok_q):    # codes: one step at most
            assert int(jnp.max(jnp.abs(mine.astype(jnp.int32)
                                       - theirs.astype(jnp.int32)))) <= 1
        tok = jnp.argmax(ref_logits, -1)[:, None].astype(jnp.int32)
    assert int(cache["pos"].min()) > W
    for key in ("slot_pos", "pos"):
        np.testing.assert_array_equal(cache[key], ref_cache[key])
    if c.get("kv_quant"):
        # compare what attention reads
        for key in ("k", "v"):
            np.testing.assert_allclose(
                tfm.dequantize_kv(cache[key], cache[key + "_scale"],
                                  jnp.float32),
                tfm.dequantize_kv(ref_cache[key], ref_cache[key + "_scale"],
                                  jnp.float32), rtol=1e-5, atol=1e-5)
    else:
        for key in ("k", "v"):
            np.testing.assert_allclose(cache[key], ref_cache[key],
                                       rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("W", [16, 256])
@pytest.mark.parametrize("rest", [(4,), ()], ids=["kv", "scale"])
def test_cache_write_tokens_writes_only_each_rows_slot(W, rest):
    """Chunked (W a multiple of 128) and whole-row writes put each
    row's token at its slot of every head, in the head-major K/V
    (L, B, Kv, W, hd) and int8 scale (L, B, Kv, W) caches, and leave
    every other entry as it was."""
    L, rows, kv = 2, 3, 2
    rng = np.random.default_rng(0)
    cache = rng.normal(size=(L, rows, kv, W) + rest).astype(np.float32)
    new = rng.normal(size=(L, rows, kv) + rest).astype(np.float32)
    slot = np.array([W - 1, 0, W // 2 + 1], np.int32)
    out = np.asarray(jax.jit(cache_write_tokens)(cache, new, slot))
    want = cache.copy()
    for b in range(rows):
        want[:, b, :, slot[b]] = new[:, b]
    np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("case", ["plain", "gqa4"])
def test_prefill_cache_decodes_like_the_full_forward(case, kv_quant):
    """A prefill of unequal lanes builds the head-major cache, and one
    decode step on it gives each row the logits that the full-sequence
    forward over prompt + token gives at that token. With int8 K/V the
    scales are (L, B, Kv, W) and the logits agree to the codes'
    rounding."""
    cfg = _config(CASES[case])
    m = build_model(cfg, fmt="float32", kv_quant=kv_quant)
    params = m.init(jax.random.PRNGKey(0))
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (B, PROMPT + 1), 0, cfg.vocab_size))
    nxt = toks[np.arange(B), LENGTHS]                 # each row's token
    _, cache = m.prefill(params, {"tokens": jnp.asarray(toks[:, :PROMPT])},
                         buf_len=16, lengths=LENGTHS)
    L, Kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    assert cache["k"].shape == (L, B, Kv, 16, hd)
    if kv_quant:
        assert cache["k_scale"].shape == (L, B, Kv, 16)
    got, _ = m.decode_step(params, jnp.asarray(nxt[:, None]), cache)
    want, _ = m.prefill(params, {"tokens": jnp.asarray(toks)},
                        buf_len=16, lengths=LENGTHS + 1)
    if kv_quant:
        rel = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        assert rel < 0.02, rel
    else:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

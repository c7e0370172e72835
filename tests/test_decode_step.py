"""The decode step reads each layer's cache where it lies and writes the
new token after the layer scan. It must compute what writing the token
first and attending over the whole buffer computes: the same keys under
one softmax, only the float summation order differs. Checked on the
CPU, in float32, against that write-then-attend step written out here,
for every transformer-family cache: plain, sliding-window ring, int8 KV
and audio cross-attention, over enough steps to wrap the ring with
lanes of unequal length."""
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


def _reference_layers(stack, x, cache, cfg, policy, window, enc_kv):
    """Write the token into its ring slot, then attend over the whole
    buffer."""
    pos, W = cache["pos"], cache["k"].shape[2]
    rows, slot = jnp.arange(B), jnp.mod(pos, W)
    slot_pos = cache["slot_pos"].at[rows, slot].set(pos)
    allow = decode_attention_mask(slot_pos, pos, window)
    quant = "k_scale" in cache
    adt = policy.activation_dtype

    def layer(x, inp):
        lp, (ck, cv, *scales), enc = inp
        xn = rms_norm(x, lp["attn_norm"])
        q, k, v = tfm._project_qkv(lp["attn"], xn, cfg, policy)
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
        if quant:
            (kq, ksc), (vq, vsc) = tfm.quantize_kv(k), tfm.quantize_kv(v)
            ck, cv = cache_write_decode(ck, cv, kq, vq, pos)
            ks = scales[0].at[rows, slot].set(ksc[:, 0])
            vs = scales[1].at[rows, slot].set(vsc[:, 0])
            kf, vf = tfm.dequantize_kv(ck, ks, adt), tfm.dequantize_kv(
                cv, vs, adt)
            out = (ck, cv, ks, vs)
        else:
            ck, cv = cache_write_decode(ck, cv, k, v, pos)
            kf, vf = ck, cv
            out = (ck, cv)
        o = attention(q, kf, vf, mask=allow[:, None, :])
        x = x + linear_apply(lp["attn"]["wo"], o.reshape(B, 1, -1), policy)
        if enc:
            x = tfm.cross_attn_block(lp, x, *enc, cfg, policy)
        x, _ = tfm.ffn_block(lp, x, cfg, policy)
        return x, out

    keys = ("k", "v", "k_scale", "v_scale") if quant else ("k", "v")
    x, out = jax.lax.scan(layer, x, (stack, tuple(cache[k] for k in keys),
                                     enc_kv or ()))
    return x, dict(cache, slot_pos=slot_pos, pos=pos + 1,
                   **dict(zip(keys, out)))


def _reference_step(m, params, tokens, cache):
    x = embed(tokens, params["embed"], m.adt)
    enc_kv = ((cache["enc_k"], cache["enc_v"])
              if m.cfg.family == "audio" else None)
    h, cache = _reference_layers(params["layers"], x, cache, m.cfg,
                                 m.policy, m.window, enc_kv)
    h = rms_norm(h, params["final_norm"])
    return m.logits(params, h[:, -1]), cache


CASES = {
    # 16-slot buffer: the ring wraps after 7-12 steps
    "plain": dict(arch="stablelm-1.6b", buf=16),
    # window 12 = the ring: the overwritten slot leaves the window
    "sliding_window": dict(arch="h2o-danube-3-4b", buf=32, window=12),
    "kv_quant": dict(arch="stablelm-1.6b", buf=16, kv_quant=True),
    "audio_cross": dict(arch="seamless-m4t-large-v2", buf=16),
}


@pytest.mark.parametrize("case", list(CASES))
def test_decode_step_matches_write_then_attend(case):
    c = CASES[case]
    cfg = get_config(c["arch"]).reduced()
    m = build_model(cfg, fmt="float32", window_override=c.get("window"),
                    kv_quant=c.get("kv_quant", False))
    params = m.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0,
                              cfg.vocab_size)
    logits, cache = m.prefill(params, batch_for(cfg, toks),
                              buf_len=c["buf"], lengths=LENGTHS)
    W = cache["k"].shape[2]
    ref_cache = cache
    step, ref_step = jax.jit(m.decode_step), jax.jit(
        lambda p, t, c: _reference_step(m, p, t, c))
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    for _ in range(W + 4):                      # every lane wraps
        logits, cache = step(params, tok, cache)
        ref_logits, ref_cache = ref_step(params, tok, ref_cache)
        np.testing.assert_allclose(logits, ref_logits, rtol=1e-5,
                                   atol=1e-5)
        tok = jnp.argmax(ref_logits, -1)[:, None].astype(jnp.int32)
    assert int(cache["pos"].min()) > W
    for key in ("slot_pos", "pos"):
        np.testing.assert_array_equal(cache[key], ref_cache[key])
    if c.get("kv_quant"):
        # the codes may round one step apart; compare what attention reads
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
def test_cache_write_tokens_writes_only_each_rows_slot(W):
    """Chunked (W a multiple of 128) and whole-row writes put each
    row's token at its slot and leave every other entry as it was."""
    L, rows, rest = 2, 3, (2, 4)
    rng = np.random.default_rng(0)
    cache = rng.normal(size=(L, rows, W) + rest).astype(np.float32)
    new = rng.normal(size=(L, rows) + rest).astype(np.float32)
    slot = np.array([W - 1, 0, W // 2 + 1], np.int32)
    out = np.asarray(jax.jit(cache_write_tokens)(cache, new, slot))
    want = cache.copy()
    want[:, np.arange(rows), slot] = new
    np.testing.assert_array_equal(out, want)

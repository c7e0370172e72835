"""Plain float32 reference of the dense decoder the configurations
describe, for the check that decides ``correct``.

It imports nothing of the program. Weights come from
:mod:`chipbench.weights`, regenerated from the seed one layer at a
time, so the reference fits beside nothing: it runs after the serving
state is freed. Every matmul runs at ``Precision.HIGHEST``.

The block, as published for Mistral and StableLM-2 (Hugging Face
``MistralForCausalLM`` / ``StableLmForCausalLM``), with the departures
the configuration file lists: pre-norm RMSNorm with a gain, rotary
embedding on the two halves of each head ("rotate half"), grouped-query
causal attention, a SwiGLU feed-forward, a final RMSNorm and an untied
output head.

Where the configuration states int8 weights (``precision.fmt ==
"int8"``), the reference quantizes the projections itself as stated:
LLM.int8 vector-wise absmax, one scale per output column over the
int8 rows, with the ``outlier_fraction`` of input rows of largest
magnitude kept in bf16, and multiplies by the dequantized weights.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.flops import dims

HI = jax.lax.Precision.HIGHEST
PROJECTIONS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "mlp/w_gate",
               "mlp/w_up", "mlp/w_down")


def int8_dequantized(w: jax.Array, outlier_fraction: float) -> jax.Array:
    """LLM.int8 vector-wise absmax round trip of one (in, out) weight,
    in float32."""
    w = w.astype(jnp.float32)
    n_out = int(round(outlier_fraction * w.shape[0]))
    keep = jnp.zeros((w.shape[0],), bool)
    if n_out:
        rows = jax.lax.top_k(jnp.max(jnp.abs(w), axis=1), n_out)[1]
        keep = keep.at[rows].set(True)
    main = jnp.where(keep[:, None], 0.0, w)
    absmax = jnp.max(jnp.abs(main), axis=0)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    codes = jnp.clip(jnp.round(main / scale), -127, 127)
    return jnp.where(keep[:, None], w, codes * scale)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x: (B, S, heads, hd) at positions 0..S-1, rotate-half form."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _block(x, w, m, eps, theta):
    b, s, _ = x.shape
    hd, h, kv = m["head_dim"], m["heads"], m["kv_heads"]
    xn = _rms(x, w["attn_norm"], eps)

    def proj(name, bias):
        y = jnp.einsum("bsd,dn->bsn", xn, w[name], precision=HI)
        return y + w[bias] if bias in w else y

    q = _rope(proj("attn/wq", "attn/bq").reshape(b, s, h, hd), theta)
    k = _rope(proj("attn/wk", "attn/bk").reshape(b, s, kv, hd), theta)
    v = proj("attn/wv", "attn/bv").reshape(b, s, kv, hd)
    q = q.reshape(b, s, kv, h // kv, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k, precision=HI) / hd ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p, v, precision=HI)
    x = x + jnp.einsum("bsn,nd->bsd", o.reshape(b, s, h * hd),
                       w["attn/wo"], precision=HI)
    xn = _rms(x, w["mlp_norm"], eps)
    g = jnp.einsum("bsd,df->bsf", xn, w["mlp/w_gate"], precision=HI)
    u = jnp.einsum("bsd,df->bsf", xn, w["mlp/w_up"], precision=HI)
    return x + jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                          w["mlp/w_down"], precision=HI)


def logits_at(config: Dict, lay: Dict, key, rows: Sequence[np.ndarray],
              targets: Sequence[np.ndarray], first: Sequence[int]
              ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Run the reference once over each token row and read, at every
    position ``p >= first[i]`` of row ``i``, the largest logit and the
    logits of ``targets[i][p - first[i]]`` (shape (positions, T)).

    ``lay`` is the served tree's layout (:func:`weights.layout`), ``key``
    the seed's key. Returns one (row max, target logits) pair per row.
    """
    m = dims(config)
    eps = float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    prec = config["precision"]
    int8 = prec["fmt"] == "int8"
    frac = float(prec.get("outlier_fraction", 0.0))
    s = max(len(r) for r in rows)
    toks = np.zeros((len(rows), s), np.int32)
    for i, r in enumerate(rows):
        toks[i, :len(r)] = r

    @jax.jit
    def layer_weights(key, index):
        w = {k: v.astype(jnp.float32)
             for k, v in weights.layer(lay, key, index).items()}
        if int8:
            for name in PROJECTIONS:
                w[name] = int8_dequantized(w[name], frac)
        return w

    block = jax.jit(lambda x, w: _block(x, w, m, eps, theta))
    embed = weights.leaf(lay, key, "embed").astype(jnp.float32)
    x = embed[jnp.asarray(toks)]
    del embed
    for index in range(m["layers"]):
        x = block(x, layer_weights(key, index))
    gain = weights.leaf(lay, key, "final_norm").astype(jnp.float32)
    head = weights.leaf(lay, key, "lm_head").astype(jnp.float32)

    @jax.jit
    def read(h, gain, head, tgt):
        lg = jnp.einsum("sd,dv->sv", _rms(h, gain, eps), head,
                        precision=HI)
        return lg.max(-1), jnp.take_along_axis(lg, tgt, axis=-1)

    out = []
    for i, r in enumerate(rows):
        tgt = np.zeros((s, targets[i].shape[1]), np.int32)
        n = len(targets[i])
        tgt[first[i]:first[i] + n] = targets[i]
        mx, lt = read(x[i], gain, head, jnp.asarray(tgt))
        out.append((np.asarray(mx)[first[i]:first[i] + n],
                    np.asarray(lt)[first[i]:first[i] + n]))
    return out


def served_rows(prompt: np.ndarray, served: Sequence[int]):
    """The reference's input for one served request: the prompt
    followed by every served token but the last, and the position
    whose logits chose the first served token."""
    row = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    return row, len(prompt) - 1

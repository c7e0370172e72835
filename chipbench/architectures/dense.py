"""The dense decoder, as published for Mistral and StableLM-2 (Hugging
Face ``MistralForCausalLM`` / ``StableLmForCausalLM``): the program's
``ModelConfig``, the plain float32 reference, and the model FLOPs of
prefill and decode.

The block, with the departures the configuration file lists: pre-norm
RMSNorm with a gain, rotary embedding on the two halves of each head
("rotate half"), grouped-query causal attention, a SwiGLU feed-forward,
a final RMSNorm and an untied output head.

The reference imports nothing of the program. Weights come from
:mod:`chipbench.weights`, regenerated from the seed one layer at a
time, so it fits beside nothing: it runs after the serving state is
freed. Every matmul runs at ``Precision.HIGHEST``. Where the
configuration states int8 weights (``precision.fmt == "int8"``), the
reference quantizes the projections itself as stated
(:func:`chipbench.reference.int8_dequantized`) and multiplies by the
dequantized weights.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.reference import HI, int8_dequantized, rms, rope

PROJECTIONS = ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "mlp/w_gate",
               "mlp/w_up", "mlp/w_down")


def model_config(config: Dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=config["name"], family="dense",
        num_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        d_ff=int(config["intermediate_size"]),
        vocab_size=int(config["vocab_size"]),
        rope_theta=float(config["rope_theta"]),
        use_bias=bool(config["attention_bias"]),
        source=config["source"])


# ---------------------------------------------------------------------------
# model FLOPs, from the configuration's shapes
# ---------------------------------------------------------------------------
def dims(config: Dict) -> Dict[str, int]:
    """The widths of a dense decoder configuration file."""
    d = int(config["hidden_size"])
    h = int(config["num_attention_heads"])
    return {
        "layers": int(config["num_hidden_layers"]),
        "d": d,
        "heads": h,
        "kv_heads": int(config["num_key_value_heads"]),
        "head_dim": int(config.get("head_dim") or d // h),
        "ff": int(config["intermediate_size"]),
        "vocab": int(config["vocab_size"]),
    }


def layer_matmuls(config: Dict) -> Dict[str, Tuple[int, int]]:
    """(in, out) of every projection of one decoder layer."""
    m = dims(config)
    q = m["heads"] * m["head_dim"]
    kv = m["kv_heads"] * m["head_dim"]
    return {"wq": (m["d"], q), "wk": (m["d"], kv), "wv": (m["d"], kv),
            "wo": (q, m["d"]), "w_gate": (m["d"], m["ff"]),
            "w_up": (m["d"], m["ff"]), "w_down": (m["ff"], m["d"])}


def matmul_params(config: Dict) -> int:
    """Weights multiplied per token: every layer's projections and the
    output head (the embedding is a lookup, not a matmul)."""
    m = dims(config)
    per_layer = sum(k * n for k, n in layer_matmuls(config).values())
    return m["layers"] * per_layer + m["d"] * m["vocab"]


def attention_flops(config: Dict, context: int) -> int:
    """Scores and weighted values of one query token over ``context``
    cached positions, all layers: 2 x 2 x heads x head_dim x context."""
    m = dims(config)
    return 4 * m["layers"] * m["heads"] * m["head_dim"] * context


def decode_token_flops(config: Dict, context: int) -> int:
    """Model FLOPs of one decoded token that attends ``context``
    positions (its own included)."""
    return 2 * matmul_params(config) + attention_flops(config, context)


def decode_flops(config: Dict, prompt_len: int, generated: int) -> int:
    """Model FLOPs of the decode steps that produced tokens 2 ..
    ``generated`` of a request (the first comes from prefill): decode
    step k attends ``prompt_len + k`` positions."""
    n = max(generated - 1, 0)
    return (n * 2 * matmul_params(config)
            + attention_flops(config, 1) * (n * prompt_len
                                             + n * (n + 1) // 2))


def prefill_flops(config: Dict, prompt_len: int) -> int:
    """Model FLOPs of prefilling one prompt: every position through the
    layers with causal attention, and the output head at the last
    position only (the one whose logits are served). Padding is not
    model work and is not counted."""
    m = dims(config)
    layer_params = matmul_params(config) - m["d"] * m["vocab"]
    causal = prompt_len * (prompt_len + 1) // 2
    return (2 * layer_params * prompt_len
            + 4 * m["layers"] * m["heads"] * m["head_dim"] * causal
            + 2 * m["d"] * m["vocab"])


# ---------------------------------------------------------------------------
# the plain float32 reference
# ---------------------------------------------------------------------------
def _block(x, w, m, eps, theta):
    b, s, _ = x.shape
    hd, h, kv = m["head_dim"], m["heads"], m["kv_heads"]
    xn = rms(x, w["attn_norm"], eps)

    def proj(name, bias):
        y = jnp.einsum("bsd,dn->bsn", xn, w[name], precision=HI)
        return y + w[bias] if bias in w else y

    q = rope(proj("attn/wq", "attn/bq").reshape(b, s, h, hd), theta)
    k = rope(proj("attn/wk", "attn/bk").reshape(b, s, kv, hd), theta)
    v = proj("attn/wv", "attn/bv").reshape(b, s, kv, hd)
    q = q.reshape(b, s, kv, h // kv, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k, precision=HI) / hd ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgst,btkh->bskgh", p, v, precision=HI)
    x = x + jnp.einsum("bsn,nd->bsd", o.reshape(b, s, h * hd),
                       w["attn/wo"], precision=HI)
    xn = rms(x, w["mlp_norm"], eps)
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
        lg = jnp.einsum("sd,dv->sv", rms(h, gain, eps), head,
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

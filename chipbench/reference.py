"""What every architecture's plain float32 reference shares, for the
check that decides ``correct``: the matmul precision, the int8 weight
round trip a configuration may state, RMSNorm, rotary embedding, and the
rows a served request is recomputed over.

Each architecture's own reference is ``logits_at`` in
``chipbench/architectures/<architecture>.py``. None of it imports the
program.

Int8, where a configuration states it (``precision.fmt == "int8"``):
LLM.int8 vector-wise absmax, one scale per output column over the int8
rows, with the ``outlier_fraction`` of input rows of largest magnitude
kept in bf16.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


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


def rms(x, gain, eps):
    """RMSNorm with a gain, over the last axis."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, theta):
    """x: (B, S, heads, hd) at positions 0..S-1, rotate-half form."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def served_rows(prompt: np.ndarray, served: Sequence[int]):
    """The reference's input for one served request: the prompt
    followed by every served token but the last, and the position
    whose logits chose the first served token."""
    row = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    return row, len(prompt) - 1

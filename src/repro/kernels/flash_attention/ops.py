"""jit'd wrapper for the flash attention kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention.kernel import flash_attention_pallas


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq",
                                             "bkv", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    bq: int = 512, bkv: int = 512, interpret: bool = False):
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  bq=bq, bkv=bkv, interpret=interpret)

"""jit'd wrapper for paged decode attention."""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.kernel import paged_attention_pallas


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                    interpret: bool = False):
    return paged_attention_pallas(q, k_pages, v_pages, page_table,
                                  seq_lens, interpret=interpret)

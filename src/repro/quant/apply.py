"""Precision-policy-dispatched linear layers + pytree post-training quant.

Every matmul in the model zoo routes through :func:`linear_apply`, which
dispatches on the parameter *representation*:

* plain array  -> jnp.dot in the policy's compute dtype,
* Int8Weight   -> LLM.int8-style dequant matmul (+outlier matmul),
* NF4Weight    -> NF4 on-the-fly dequant matmul.

When ``policy.use_pallas_kernels`` is set (an executed run on a TPU
sets it, see :meth:`repro.api.ExperimentSpec.build_engine`), quantized
matmuls run through the compiled Pallas ``quant_matmul`` kernel instead
of the pure-jnp reference path.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.core.precision import (PrecisionPolicy, INT8, NF4)
from repro.quant.int8 import Int8Weight, quantize_int8, int8_matmul, \
    dequantize_int8
from repro.quant.nf4 import NF4Weight, quantize_nf4, nf4_matmul, \
    dequantize_nf4


def linear_init(key, in_dim: int, out_dim: int, dtype=jnp.float32,
                scale: float | None = None) -> jnp.ndarray:
    """He/lecun-style init for a (in, out) weight."""
    if scale is None:
        scale = in_dim ** -0.5
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32)
            * scale).astype(dtype)


def dequantize_weight(w: Any, dtype=jnp.bfloat16) -> jnp.ndarray:
    if isinstance(w, Int8Weight):
        return dequantize_int8(w, dtype)
    if isinstance(w, NF4Weight):
        return dequantize_nf4(w, dtype)
    return w.astype(dtype)


def linear_apply(w: Any, x: jnp.ndarray,
                 policy: PrecisionPolicy) -> jnp.ndarray:
    """y = x @ w under the precision policy.

    For 16-bit policies the dot's OUTPUT type is the compute dtype: on
    TPU the MXU still accumulates partial products in f32 internally,
    but row-parallel (TP) partial sums then cross shards in bf16 —
    halving every tensor-parallel all-reduce (fwd and cotangent). This
    is the Megatron-style bf16-reduction tradeoff. f32 policies keep
    f32 end-to-end.
    """
    cd = policy.compute_dtype
    if isinstance(w, Int8Weight):
        if policy.use_pallas_kernels:
            from repro.kernels.quant_matmul import ops as qops
            return qops.int8_matmul_kernel(x, w, compute_dtype=cd)
        return int8_matmul(x, w, cd)
    if isinstance(w, NF4Weight):
        if policy.use_pallas_kernels:
            from repro.kernels.quant_matmul import ops as qops
            return qops.nf4_matmul_kernel(x, w, compute_dtype=cd)
        return nf4_matmul(x, w, cd)
    acc = jnp.float32 if cd == jnp.float32 else cd
    return jnp.einsum("...k,kn->...n", x.astype(cd), w.astype(cd),
                      preferred_element_type=acc).astype(cd)


# ---------------------------------------------------------------------------
# pytree post-training quantization (paper §2: bitsandbytes PTQ of the
# feed-forward and attention projection weights)
# ---------------------------------------------------------------------------
_QUANTIZABLE_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                     "w_in", "w_out", "experts_gate", "experts_up",
                     "experts_down")
_MIN_QUANT_DIM = 32     # skip tiny weights (norms, biases, dt, A, conv)


def _quantize_leaf(path: str, leaf: Any, policy: PrecisionPolicy) -> Any:
    if not isinstance(leaf, jnp.ndarray) or leaf.ndim < 2:
        return leaf
    name = path.split("/")[-1]
    if name not in _QUANTIZABLE_KEYS:
        return leaf
    if leaf.shape[-1] < _MIN_QUANT_DIM or leaf.shape[-2] < _MIN_QUANT_DIM:
        return leaf

    def q2d(w2d):
        if policy.fmt == INT8:
            return quantize_int8(w2d, policy.outlier_fraction)
        blk = policy.nf4_block_size
        while w2d.shape[0] % blk or blk % 2:
            blk //= 2
        return quantize_nf4(w2d, max(blk, 2))

    if leaf.ndim == 2:
        return q2d(leaf)
    # stacked (layers, in, out) or (layers, experts, in, out): quantize
    # each slice; stays a stacked pytree so lax.scan over layers works.
    lead = leaf.shape[:-2]
    flat = leaf.reshape((-1,) + leaf.shape[-2:])
    qs = [q2d(flat[i]) for i in range(flat.shape[0])]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs).reshape(
        lead + xs[0].shape), *qs)
    return stacked


def quantize_params(params: Dict, policy: PrecisionPolicy) -> Dict:
    """Post-training-quantize attention/FFN projection weights in a tree."""
    if policy.fmt not in (INT8, NF4):
        return params

    def walk(tree, path=""):
        if isinstance(tree, dict):
            return {k: walk(v, f"{path}/{k}") for k, v in tree.items()}
        return _quantize_leaf(path, tree, policy)

    return walk(params)

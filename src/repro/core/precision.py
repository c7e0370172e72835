"""Numerical-precision policy — the paper's first lever (§3).

A :class:`PrecisionPolicy` is threaded through every linear layer in the
model zoo. It controls

* the *storage* format of weights (fp32 / bf16 / fp16 / int8 / nf4),
* the *compute* dtype fed to the MXU (always a float type — integer
  formats are dequantized on the fly, exactly as bitsandbytes does on
  GPU and as our Pallas ``quant_matmul`` kernel does on TPU),
* bookkeeping the energy model needs: bits per weight, whether a
  dequantization pass (extra kernel launches + extra bytes moved) is
  incurred, and whether the format activates the MXU fast path.

The paper's central precision finding is *phase-dependence*: low-precision
formats only pay off in compute-bound regimes; in memory-bound decode the
dequant overhead can make int8 2–3x WORSE than fp32.  The fields here are
what lets :mod:`repro.core.energy` reproduce that mechanism.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

# Formats supported (mirrors the paper's five formats).
FLOAT32 = "float32"
FLOAT16 = "float16"
BFLOAT16 = "bfloat16"
INT8 = "int8"      # LLM.int8-style vector-wise absmax + outlier split
NF4 = "nf4"        # QLoRA NormalFloat4, block-wise, packed 2/byte

ALL_FORMATS = (FLOAT32, FLOAT16, BFLOAT16, INT8, NF4)
QUANTIZED_FORMATS = (INT8, NF4)


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Numerical policy for one model instantiation."""

    fmt: str = BFLOAT16
    # Compute dtype fed to the MXU after (de)quantization.
    compute_dtype: jnp.dtype = jnp.bfloat16
    # Activations / residual stream dtype.
    activation_dtype: jnp.dtype = jnp.bfloat16
    # int8: fraction of columns treated as outliers and kept in 16-bit
    # (LLM.int8's outlier decomposition; paper cites Dettmers et al. 2022).
    outlier_fraction: float = 0.01
    # nf4: quantization block size along the input dim.
    nf4_block_size: int = 64
    # Route quantized matmuls through the compiled Pallas kernel (the
    # TPU default of models.build_model) instead of the pure-jnp path.
    use_pallas_kernels: bool = False

    # ---- derived quantities used by the energy model -------------------
    @property
    def weight_bits(self) -> float:
        return {
            FLOAT32: 32.0,
            FLOAT16: 16.0,
            BFLOAT16: 16.0,
            INT8: 8.0,
            # 4-bit codes + fp16 absmax per block (double quant ignored)
            NF4: 4.0 + 16.0 / self.nf4_block_size,
        }[self.fmt]

    @property
    def is_quantized(self) -> bool:
        return self.fmt in QUANTIZED_FORMATS

    @property
    def needs_dequant_pass(self) -> bool:
        """Integer formats are unpacked/dequantized before every matmul."""
        return self.is_quantized

    @property
    def tensor_core_path(self) -> bool:
        """Whether the format activates the fast matrix unit path.

        On H100 fp16/bf16/int8 hit Tensor Cores; on TPU the MXU natively
        consumes bf16 (fp32 runs at ~1/4 throughput through the MXU).
        fp32 is the slow path in both worlds.
        """
        return self.fmt != FLOAT32

    @property
    def param_dtype(self) -> jnp.dtype:
        """dtype in which *master* params are stored before quantization."""
        return {
            FLOAT32: jnp.float32,
            FLOAT16: jnp.float16,
            BFLOAT16: jnp.bfloat16,
            INT8: jnp.bfloat16,
            NF4: jnp.bfloat16,
        }[self.fmt]


def make_policy(fmt: str, use_pallas_kernels: bool = False,
                compute_dtype: Optional[jnp.dtype] = None) -> PrecisionPolicy:
    if fmt not in ALL_FORMATS:
        raise ValueError(f"unknown precision format {fmt!r}; "
                         f"expected one of {ALL_FORMATS}")
    if compute_dtype is None:
        compute_dtype = jnp.float32 if fmt == FLOAT32 else jnp.bfloat16
    act = jnp.float32 if fmt == FLOAT32 else jnp.bfloat16
    return PrecisionPolicy(fmt=fmt, compute_dtype=compute_dtype,
                           activation_dtype=act,
                           use_pallas_kernels=use_pallas_kernels)

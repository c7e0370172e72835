"""Compile guards: the main serving path at stablelm-1.6b widths,
compiled for a described (not attached) TPU v5e.

Nothing runs: each test hands the TPU compiler shapes only, and fails
on what the chip's compiler would refuse (VMEM overflow, block shapes
off the (8, 128) tiling, operations Mosaic cannot lower). The topology
is described inside a module fixture, never at import time, so every
test worker collects the same tests and only the worker given this
file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.quant_matmul import ops as qops
from repro.models import build_model
from repro.quant.int8 import Int8Weight
from repro.quant.nf4 import NF4Weight

CFG = get_config("stablelm-1.6b")
D, F = CFG.d_model, CFG.d_ff
MAX_BATCH, BUF_LEN, PREFILL_LEN = 8, 1024, 256
V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    """Shape-only stand-ins of ``tree`` placed on ``sharding``."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _weights(fmt: str, k: int, n: int):
    if fmt == "int8":
        n_out = round(0.01 * k)
        return Int8Weight(
            jax.ShapeDtypeStruct((k, n), jnp.int8),
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n_out,), jnp.int32),
            jax.ShapeDtypeStruct((n_out, n), jnp.bfloat16))
    return NF4Weight(jax.ShapeDtypeStruct((k // 2, n), jnp.uint8),
                     jax.ShapeDtypeStruct((k // 64, n), jnp.float32))


# 8 rows: a decode batch; 520 and 4160 = 8 prompts x 520 tokens are not
# multiples of the 256-row block (a whole-matrix block at 4160 overflows
# VMEM), so they split into row blocks that are not powers of two
@pytest.mark.parametrize("rows", [8, 520, 4160])
@pytest.mark.parametrize("fmt", ["int8", "nf4"])
@pytest.mark.parametrize("k,n", [(D, F), (F, D)], ids=["up", "down"])
def test_quant_matmul_compiles(one_chip, fmt, rows, k, n):
    kernel = (qops.int8_matmul_kernel if fmt == "int8"
              else qops.nf4_matmul_kernel)
    x = jax.ShapeDtypeStruct((rows, k), jnp.bfloat16)
    compiled = _compile(lambda x, q: kernel(x, q, interpret=False),
                        *_on(one_chip, (x, _weights(fmt, k, n))))
    assert "tpu_custom_call" in compiled.as_text()


def _model_args(one_chip, model, quantize: bool = False):
    def init(key):
        p = model.init(key)
        return model.quantize(p) if quantize else p
    params = jax.eval_shape(init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(MAX_BATCH, BUF_LEN))
    return _on(one_chip, params), _on(one_chip, cache)


def _fits_one_chip(compiled) -> int:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
    return used


def test_full_width_bf16_prefill_compiles(one_chip):
    model = build_model(CFG, fmt="bfloat16", use_pallas_kernels=False)
    params, _ = _model_args(one_chip, model)
    toks, lens = _on(one_chip, (
        jax.ShapeDtypeStruct((MAX_BATCH, PREFILL_LEN), jnp.int32),
        jax.ShapeDtypeStruct((MAX_BATCH,), jnp.int32)))
    compiled = _compile(
        lambda p, t, n: model.prefill(p, {"tokens": t}, buf_len=BUF_LEN,
                                      lengths=n), params, toks, lens)
    _fits_one_chip(compiled)


@pytest.mark.parametrize("fmt", ["bfloat16", "int8", "nf4"])
def test_full_width_decode_step_compiles(one_chip, fmt):
    """The served decode step; quantized formats must hold the
    compiled Pallas kernel."""
    model = build_model(CFG, fmt=fmt, use_pallas_kernels=fmt != "bfloat16")
    params, cache = _model_args(one_chip, model, quantize=True)
    toks = _on(one_chip, jax.ShapeDtypeStruct((MAX_BATCH, 1), jnp.int32))
    compiled = _compile(model.decode_step, params, toks, cache)
    _fits_one_chip(compiled)
    assert ("tpu_custom_call" in compiled.as_text()) == (fmt != "bfloat16")

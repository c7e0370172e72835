"""Compile guards: the main serving path at stablelm-1.6b widths,
compiled for a described (not attached) TPU v5e.

Nothing runs: each test hands the TPU compiler shapes only, and fails
on what the chip's compiler would refuse (VMEM overflow, block shapes
off the (8, 128) tiling, operations Mosaic cannot lower). The topology
is described inside a module fixture, never at import time, so every
test worker collects the same tests and only the worker given this
file loads the TPU library.
"""
import json
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.quant_matmul import ops as qops
from repro.models import build_model
from repro.quant.int8 import Int8Weight
from repro.quant.nf4 import NF4Weight
from repro.serving.backend import jit_decode_step

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))                   # the benchmark's configs
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


def _model_args(one_chip, model, quantize: bool = False,
                lanes: int = MAX_BATCH, buf_len: int = BUF_LEN):
    def init(key):
        p = model.init(key)
        return model.quantize(p) if quantize else p
    params = jax.eval_shape(init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(lanes, buf_len))
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


# the chat cell's decode: 32 lanes over a 640-slot buffer
CHAT_LANES, CHAT_BUF = 32, 640


def _kv_shapes(cfg, lanes: int, buf_len: int):
    """HLO shapes of the head-major K or V cache (L, B, Kv, W, hd): (the
    whole cache, one layer's slice with and without its unit layer
    axis), and one lane's 128-slot chunk of every layer, which is what
    an in-place token write updates."""
    layer = f"{lanes},{cfg.num_kv_heads},{buf_len},{cfg.head_dim}]"
    return ((f"bf16[{cfg.num_layers},{layer}", f"bf16[1,{layer}",
             f"bf16[{layer}"),
            f"bf16[{cfg.num_layers},1,{cfg.num_kv_heads},128,"
            f"{cfg.head_dim}]")


CACHE_SHAPES, TOKEN_CHUNK = _kv_shapes(CFG, CHAT_LANES, CHAT_BUF)
_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = (\w+\[[\d,]*\])\S* "
                    r"(copy|scatter|dynamic-update-slice|transpose"
                    r"|dynamic-slice)\((.*)")
_COMPUTATION = re.compile(r"(?:ENTRY )?%(\S+) \(.*\{$")


def _computations(hlo: str):
    """{name: (instruction lines, is fused)}: a computation is fused
    when a ``fusion`` instruction calls it."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name and line.startswith("  "):
            comps[name].append(line)
    fused = set(re.findall(r" fusion\(.*?calls=%([^\s,]+)", hlo))
    return {n: (lines, n in fused) for n, lines in comps.items()}


def _materialized(hlo: str):
    """Instructions whose result is written to device memory: every one
    outside a fused computation, and the root of a fused computation
    that an instruction outside one calls."""
    comps = _computations(hlo)
    out = []
    for lines, fused in comps.values():
        if fused:
            continue
        out += lines
        for line in lines:
            m = re.search(r" fusion\(.*?calls=%([^\s,]+)", line)
            if m:
                out += [c for c in comps[m.group(1)][0]
                        if c.lstrip().startswith("ROOT ")]
    return out


def _cache_sized_writes(hlo: str, cache_shapes, token_chunk):
    """(token writes, other writes): every copy, scatter,
    dynamic-update-slice or transpose, fused or not, and every
    materialized dynamic-slice, whose result is the whole K or V cache
    or one layer of it. A token write is a dynamic-update-slice of a
    ``token_chunk``. A dynamic-slice inside a fusion is the fusion
    reading the cache where it lies."""
    shapes = dict(re.findall(r"%(\S+) = (\w+\[[\d,]*\])", hlo))
    stored = set(_materialized(hlo))
    tokens, other = [], []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(2) not in cache_shapes:
            continue
        if m.group(3) == "dynamic-slice" and line not in stored:
            continue
        update = re.findall(r"%([^\s,)]+)", m.group(4))[1:2]
        is_token = (m.group(3) == "dynamic-update-slice"
                    and [shapes.get(u) for u in update] == [token_chunk])
        (tokens if is_token else other).append(line.strip())
    return tokens, other


def _slot_index_known_zero_bits(write: str) -> int:
    """The low bits of a token write's slot index that the compiler
    knows are zero (its ``index_known_bits``); the slot axis of the
    head-major cache is axis 3."""
    known = json.loads(re.search(r'"index_known_bits":(\[.*?\])',
                                 write).group(1))
    return int(known[3]["zeroes"])


def _assert_reads_and_writes_in_place(compiled, cache, kv_shapes):
    """Attention reads each layer's slice where it lies: no layer of K
    or V is written to memory. The new tokens go into the donated cache
    in place, on 128-slot boundaries, and no cache-sized copy,
    relayout or scatter is left."""
    cache_shapes, token_chunk = kv_shapes
    hlo = compiled.as_text()
    results = (re.search(r"= (\w+\[[\d,]*\])", line)
               for line in _materialized(hlo))
    assert [r.string.strip()[:160] for r in results
            if r and r.group(1) in cache_shapes[1:]] == []
    tokens, other = _cache_sized_writes(hlo, cache_shapes, token_chunk)
    assert [w[:160] for w in other] == []
    assert len(tokens) == 2                     # K and V
    # the compiler must see that each write starts on a 128-slot
    # boundary: behind JAX's wrap of negative indices it did not, and
    # the same writes ran 3.7x slower on a v5e
    assert all(_slot_index_known_zero_bits(w) & 127 == 127 for w in tokens)
    mem = compiled.memory_analysis()
    kv_bytes = cache["k"].size * 2 + cache["v"].size * 2
    assert mem.alias_size_in_bytes >= kv_bytes
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize("fmt", ["bfloat16", "int8"])
def test_served_decode_step_writes_the_cache_in_place(one_chip, fmt):
    """The decode step as the executed backend serves it (cache
    donated), at the chat cell's shapes (MHA, head_dim 64)."""
    model = build_model(CFG, fmt=fmt, use_pallas_kernels=fmt != "bfloat16")
    params, cache = _model_args(one_chip, model, quantize=True,
                                lanes=CHAT_LANES, buf_len=CHAT_BUF)
    toks = _on(one_chip, jax.ShapeDtypeStruct((CHAT_LANES, 1), jnp.int32))
    compiled = jit_decode_step(model).lower(params, toks, cache).compile()
    _assert_reads_and_writes_in_place(compiled, cache,
                                      (CACHE_SHAPES, TOKEN_CHUNK))


def test_served_gqa_decode_step_reads_the_cache_in_place(one_chip):
    """The same at the Mistral cell's shapes: GQA 32 / 8 heads of 128,
    16 layers, int8 weights through the Pallas kernel, 48 lanes over a
    1536-slot buffer. Here the score and value dots run on the MXU; with
    the cache sequence-major the compiler copied each layer's K and V
    out of the stack before them (578 MB of temporaries)."""
    from chipbench.architectures import dense
    conf = json.loads((ROOT / "chipbench/configs/mistral-7b-16L-int8.json")
                      .read_text())
    cfg = dense.model_config(conf)
    lanes, buf_len = 48, 1536
    model = build_model(cfg, fmt=conf["precision"]["fmt"],
                        use_pallas_kernels=True)
    params, cache = _model_args(one_chip, model, quantize=True,
                                lanes=lanes, buf_len=buf_len)
    toks = _on(one_chip, jax.ShapeDtypeStruct((lanes, 1), jnp.int32))
    compiled = jit_decode_step(model).lower(params, toks, cache).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_reads_and_writes_in_place(
        compiled, cache, _kv_shapes(cfg, lanes, buf_len))

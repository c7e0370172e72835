"""Real-compute microbenchmarks (CPU wall-time): kernels in interpret
mode vs their jnp references, and one reduced-model serve/train step.
These give honest measured us_per_call numbers alongside the modeled
energy benches."""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from benchmarks.common import Row, timeit, save_results
from repro.configs import get_config
from repro.models import build_model
from repro.quant import quantize_int8
from repro.kernels.quant_matmul.kernel import int8_matmul_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.training import adamw_init, make_train_step


def run() -> List[Row]:
    rows: List[Row] = []
    k = jax.random.PRNGKey(0)

    # int8 kernel vs fused-jnp dequant matmul
    x = jax.random.normal(k, (64, 512), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 256)) * 0.05
    q = quantize_int8(w)
    f_kernel = jax.jit(lambda a: int8_matmul_pallas(
        a, q.codes, q.scale, bm=64, bn=256, bk=256, interpret=True))
    f_ref = jax.jit(lambda a: jnp.dot(
        a, q.codes.astype(jnp.float32) * q.scale[None, :]))
    f_kernel(x).block_until_ready()
    f_ref(x).block_until_ready()
    rows.append(Row("micro/int8_kernel_interpret",
                    timeit(lambda: f_kernel(x).block_until_ready()),
                    "pallas interpret mode (CPU emulation)"))
    rows.append(Row("micro/int8_xla_fused",
                    timeit(lambda: f_ref(x).block_until_ready()),
                    "XLA-fused dequant+dot reference"))

    # flash attention kernel vs jnp chunked attention
    B, S, H, Kv, d = 1, 256, 4, 2, 64
    qq = jax.random.normal(k, (B, S, H, d), jnp.float32)
    kk = jax.random.normal(jax.random.PRNGKey(2), (B, S, Kv, d))
    vv = jax.random.normal(jax.random.PRNGKey(3), (B, S, Kv, d))
    f_fl = jax.jit(lambda a, b, c: flash_attention_pallas(
        a, b, c, bq=64, bkv=64, interpret=True))
    f_fl(qq, kk, vv).block_until_ready()
    rows.append(Row("micro/flash_attention_interpret",
                    timeit(lambda: f_fl(qq, kk, vv).block_until_ready()),
                    f"S={S} causal"))

    # reduced-model serve + train step wall time
    cfg = get_config("minitron-8b").reduced()
    m = build_model(cfg, fmt="float32")
    params = m.init(k)
    toks = jnp.zeros((2, 32), jnp.int32)
    _, cache = m.prefill(params, {"tokens": toks}, buf_len=64)
    step_tok = jnp.ones((2, 1), jnp.int32)
    dec = jax.jit(m.decode_step)
    dec(params, step_tok, cache)[0].block_until_ready()
    rows.append(Row("micro/reduced_decode_step",
                    timeit(lambda: dec(params, step_tok,
                                       cache)[0].block_until_ready()),
                    f"{cfg.name}"))
    tstep = jax.jit(make_train_step(m))
    opt = adamw_init(params)
    batch = {"tokens": toks, "labels": toks}
    out = tstep(params, opt, batch)
    out[2]["lm_loss"].block_until_ready()
    rows.append(Row("micro/reduced_train_step",
                    timeit(lambda: tstep(params, opt, batch)[2]
                           ["lm_loss"].block_until_ready()),
                    f"{cfg.name}"))
    save_results("microbench", [r.__dict__ for r in rows])
    return rows

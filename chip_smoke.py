#!/usr/bin/env python3
"""Bring-up smoke: serve stablelm-1.6b at full width on one TPU.

Drives the executed serving path -- ``ExperimentSpec(backend="executed")``
-> ``ServeEngine`` -> ``ExecutedBackend`` -> ``models`` -> ``quant`` ->
``kernels/quant_matmul`` -- on the full stablelm-1.6b config (24 layers,
d_model 2048, vocab 100352) with random weights from ``PRNGKey(0)``, in
this one process on one chip. Phases, in order:

1. device check: exits non-zero unless JAX's devices are TPUs;
2. bf16 serve: 16 requests on a seeded Poisson stream; every request
   completes with exactly ``max_new_tokens`` ids in ``[0, vocab)``;
3. reference: one served request's prompt, prefilled and decoded for 8
   steps at batch 1 outside the engine, in bf16 and with the same
   weights upcast to float32 under matmul precision "highest", both fed
   the ids that request was served; the largest absolute logit
   difference is held to ``REF_ATOL``, and every served id to within
   ``2 * REF_ATOL`` of the float32 row's largest logit;
4. int8 and nf4: 4 requests each with really quantized weights, served
   through the compiled Pallas ``quant_matmul`` kernel (the decode
   step's HLO holds ``tpu_custom_call``); the kernel is also compared
   with ``quant_matmul/ref.py`` on one layer's weights.

Each phase frees what it placed on the device before the next starts.

    python chip_smoke.py

Lines before the last are smoke output, not metrics. The last line is
one JSON object naming the device. Any failed check exits non-zero.
"""
from __future__ import annotations

import contextlib
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

MODEL = "stablelm-1.6b"
DEVICE = "tpu-v5e"
MAX_BATCH = 8
BUF_LEN = 1024
# every prompt pads to the same prefill length (256), so only the
# prefill batch sizes add compiled shapes
PROMPT_RANGE = (249, 256)
OUTPUT_RANGE = (16, 64)
ARRIVAL_RATE_PER_S = 100.0
N_SERVE = 16
N_QUANT = 4

REF_DECODE_STEPS = 8
# largest |logit_bf16 - logit_f32| over the prefill and the 8 decode
# steps; bf16 keeps 8 mantissa bits, and the logits of these random
# weights are O(1)
REF_ATOL = 0.25
# kernel vs ref.py: largest |difference| over the largest |ref|; the
# kernel rounds its activations, dequantized weights and output to bf16
KERNEL_RTOL = 1e-2
KERNEL_ROWS = (8, 520)          # a decode batch; prefill rows % 256 != 0


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


def device_check():
    """JAX's devices, refusing anything but a TPU."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found: JAX's first device is "
            f"{dev.platform!r} ({dev.device_kind}); this smoke runs only "
            "on a TPU")
    return dev, len(devices)


def import_repro() -> None:
    """Import the package from this checkout's ``src/``, and nowhere
    else."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no src/repro next to {__file__}")
    sys.path.insert(0, str(src))


class CompileCounter:
    """Counts JAX compilations (cache loads included) and persistent
    compile-cache hits from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax
        self.compiles = self.cache_hits = 0

        def on_duration(name, _secs, **_kw):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(name, **_kw):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@contextlib.contextmanager
def phase(name: str, dev, counter: CompileCounter):
    import jax
    c0, h0, t0 = counter.compiles, counter.cache_hits, time.perf_counter()
    say(f"--- {name} ---")
    yield
    wall = time.perf_counter() - t0
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    stats = dev.memory_stats() or {}
    say(f"{name}: wall_s={wall:.1f} compiles={counter.compiles - c0} "
        f"compile_cache_hits={counter.cache_hits - h0} "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')} "
        f"live_bytes_after={live}")
    check(live < 1 << 30,
          f"{name} left {live} bytes of arrays on the device")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def serve(fmt: str, n_requests: int):
    """Serve ``n_requests`` through the executed backend and check every
    generation; quantized formats also check the kernel path. Returns
    the last request (prompt and served ids, on the host)."""
    from repro.api import ExperimentSpec
    spec = ExperimentSpec(
        model=MODEL, fmt=fmt, device=DEVICE, backend="executed",
        n_requests=n_requests, arrival="poisson",
        arrival_params={"rate_per_s": ARRIVAL_RATE_PER_S},
        prompt_range=PROMPT_RANGE, output_range=OUTPUT_RANGE,
        max_batch=MAX_BATCH, buf_len=BUF_LEN, seed=0)
    cfg = spec.model_config()
    say(f"{MODEL} {fmt}: layers={cfg.num_layers} d_model={cfg.d_model} "
        f"vocab={cfg.vocab_size} max_batch={MAX_BATCH} buf_len={BUF_LEN}")
    # the engine ExperimentSpec.run() builds; kept here so the checks
    # below can reach its backend and requests
    engine = spec.build_engine()
    report = engine.run(spec.requests())
    reqs = report.requests
    check(len(reqs) == n_requests and len(report.completed) == n_requests,
          f"{len(report.completed)} of {n_requests} requests completed")
    for r in reqs:
        check(len(r.generated) == r.max_new_tokens,
              f"request {r.req_id}: {len(r.generated)} ids generated, "
              f"max_new_tokens={r.max_new_tokens}")
        check(all(0 <= t < cfg.vocab_size for t in r.generated),
              f"request {r.req_id}: an id outside [0, {cfg.vocab_size})")
    say(f"{fmt}: {len(report.completed)}/{n_requests} requests completed, "
        f"tokens_generated={sum(len(r.generated) for r in reqs)}")
    if fmt in ("int8", "nf4"):
        check_quantized(engine.backend, fmt)
    return reqs[-1]


def check_quantized(backend, fmt: str) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.quant_matmul import ops as qops
    from repro.kernels.quant_matmul.ref import (int8_weight_matmul_ref,
                                                nf4_matmul_ref)
    from repro.quant.int8 import Int8Weight
    from repro.quant.nf4 import NF4Weight
    wtype = Int8Weight if fmt == "int8" else NF4Weight
    model, params = backend.model, backend.params
    check(model.policy.use_pallas_kernels,
          f"{fmt} model does not route through the Pallas kernel")
    n_q = sum(isinstance(leaf, wtype) for leaf in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, wtype)))
    check(n_q > 0, f"no {wtype.__name__} in the served {fmt} weights")
    say(f"{fmt}: {n_q} stacked weight tensors are {wtype.__name__}")

    hlo = jax.jit(model.decode_step).lower(
        params, backend.slot_tokens, backend.cache).compile().as_text()
    n_calls = hlo.count("tpu_custom_call")
    check(n_calls > 0, f"{fmt} decode step has no tpu_custom_call")
    say(f"{fmt}: compiled decode step HLO holds tpu_custom_call "
        f"x{n_calls}")

    # one layer's weights: the FFN up-projection of layer 0
    q = jax.tree.map(lambda a: a[0], params["layers"]["mlp"]["w_up"])
    kernel = jax.jit(qops.int8_matmul_kernel if fmt == "int8"
                     else qops.nf4_matmul_kernel)
    for rows in KERNEL_ROWS:
        x = jax.random.normal(jax.random.PRNGKey(rows),
                              (rows, model.cfg.d_model), jnp.bfloat16)
        out = kernel(x, q).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            ref = (int8_weight_matmul_ref(x, q) if fmt == "int8"
                   else nf4_matmul_ref(x, q.packed, q.absmax))
        err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
        say(f"{fmt}: kernel vs ref.py on layer-0 w_up, rows={rows}: "
            f"max|diff|/max|ref|={err:.3e} (tolerance {KERNEL_RTOL})")
        check(err <= KERNEL_RTOL, f"{fmt} kernel disagrees with ref.py")


def _fed_logits(model, params, prompt, feed):
    """Prefill ``prompt`` at batch 1, then one decode step per id of
    ``feed``; returns the stacked logits (one row more than ``feed``)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    buf = prompt.shape[0] + len(feed) + 8
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t},
                                                 buf_len=buf))
    decode = jax.jit(model.decode_step)
    logits, cache = prefill(params, jnp.asarray(prompt[None], jnp.int32))
    out = [np.asarray(logits[0])]
    for tok in feed:
        logits, cache = decode(params, jnp.full((1, 1), tok, jnp.int32),
                               cache)
        out.append(np.asarray(logits[0]))
    return np.stack(out)


def reference(cfg, request) -> float:
    """Logits of one served request's prompt and served ids, recomputed
    at batch 1 in bf16 and in float32 ("highest"): the two must agree,
    and each served id must be a greedy choice of the float32 logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import build_model
    prompt = np.asarray(request.prompt, np.int32)
    served = [int(t) for t in request.generated[:REF_DECODE_STEPS + 1]]
    check(len(served) == REF_DECODE_STEPS + 1,
          f"request {request.req_id} was served {len(served)} ids")
    m16 = build_model(cfg, fmt="bfloat16")
    p16 = m16.init(jax.random.PRNGKey(0))
    l16 = _fed_logits(m16, p16, prompt, served[:-1])
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p16)
    del p16
    with jax.default_matmul_precision("highest"):
        l32 = _fed_logits(build_model(cfg, fmt="float32"), p32, prompt,
                          served[:-1])
    del p32
    check(bool(np.isfinite(l16).all()), "NaN or inf in the bf16 logits")
    check(bool(np.isfinite(l32).all()), "NaN or inf in the float32 logits")
    diff = float(np.max(np.abs(l16 - l32)))
    agree = int(np.sum(np.argmax(l16, -1) == np.argmax(l32, -1)))
    rows = np.arange(len(served))
    gap = float(np.max(l32.max(-1) - l32[rows, served]))
    served_argmax = int(np.sum(np.argmax(l16, -1) == served))
    say(f"reference: request {request.req_id} (prompt {len(prompt)} "
        f"tokens), {len(l16)} logit rows (prefill + {REF_DECODE_STEPS} "
        f"decode fed the served ids), max|bf16 - f32|={diff:.4f} "
        f"(tolerance {REF_ATOL}), max|f32 logit|="
        f"{float(np.max(np.abs(l32))):.3f}, bf16/f32 argmax agreement "
        f"{agree}/{len(l16)}")
    say(f"reference: served ids equal to the batch-1 bf16 argmax "
        f"{served_argmax}/{len(served)}; largest f32 logit gap below the "
        f"row max of a served id {gap:.4f} (tolerance {2 * REF_ATOL})")
    check(diff <= REF_ATOL, "bf16 logits disagree with the float32 "
          "reference")
    # the engine's batched bf16 logits and the float32 ones differ by at
    # most REF_ATOL each way, so a served argmax sits within 2 * REF_ATOL
    # of the float32 row max; a wrong slot or lane lands far below it
    check(gap <= 2 * REF_ATOL, "a served id is not a greedy choice of "
          "the float32 reference")
    return diff


def main() -> None:
    dev, count = device_check()
    import_repro()
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    say("(smoke output, not metrics)")
    say(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={count} compile_cache={cache_dir}")
    cfg = get_config(MODEL)
    check(cfg.num_layers == 24 and cfg.d_model == 2048,
          f"{MODEL} is not the full config: {cfg}")
    with phase("bf16 serve", dev, counter):
        served = serve("bfloat16", N_SERVE)
    with phase("reference", dev, counter):
        reference(cfg, served)
    for fmt in ("int8", "nf4"):
        with phase(f"{fmt} serve", dev, counter):
            serve(fmt, N_QUANT)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()

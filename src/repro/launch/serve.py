"""Serving launcher: one :class:`~repro.api.ExperimentSpec` from the
command line.

* default: serve ``--arch`` at its full config through the executed
  backend (random weights from ``PRNGKey(0)``, quantized when ``--fmt``
  is int8 or nf4) on JAX's default device, printing the phase-aware
  report. On a TPU the run bills that chip's DeviceSpec.
* ``--reduced``: the same path on ``cfg.reduced()``, for CPU runs.
* ``--sim``: analytic simulation of the config (no device compute) on
  the spec's paper workload — how the paper-scale serving studies run.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b
    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \\
        --reduced --n 4
    PYTHONPATH=src python -m repro.launch.serve --arch minitron-8b --sim \\
        --pattern fixed --interval-ms 20 --n 500
"""
from __future__ import annotations

import argparse

from repro.api import ExperimentSpec, RunResult

#: (prompt_range, output_range, buf_len) of the executed modes: narrow
#: prompts keep the padded prefill shapes few
EXECUTED_SHAPES = {
    "full": ((240, 256), (16, 64), 1024),
    "reduced": ((8, 24), (4, 12), 64),
}


def _arrival(pattern: str, dt: float):
    return {
        "burst": ("all_at_once", {}),
        "fixed": ("fixed", {"interval_s": dt}),
        "random": ("uniform", {"low_s": 0.0, "high_s": 2 * dt}),
        "poisson": ("poisson", {"rate_per_s": 1.0 / max(dt, 1e-6)}),
    }[pattern]


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--fmt", default="bfloat16")
    ap.add_argument("--n", type=int, default=24)
    ap.add_argument("--pattern", default="burst",
                    choices=["burst", "fixed", "random", "poisson"])
    ap.add_argument("--interval-ms", type=float, default=20.0)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--mode", default="continuous",
                    choices=["continuous", "sequential"])
    ap.add_argument("--reduced", action="store_true",
                    help="serve cfg.reduced() (CPU-sized)")
    ap.add_argument("--sim", action="store_true",
                    help="analytic simulation, no device compute")
    return ap


def build_spec(argv=None) -> ExperimentSpec:
    args = _parser().parse_args(argv)
    arrival, params = _arrival(args.pattern, args.interval_ms / 1e3)
    kw = dict(model=args.arch, fmt=args.fmt, reduced=args.reduced,
              mode=args.mode, max_batch=args.max_batch, n_requests=args.n,
              arrival=arrival, arrival_params=params)
    if args.sim:
        return ExperimentSpec(backend="analytic", **kw)
    import jax
    from repro.core.hardware import device_for_kind
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        kw["device"] = device_for_kind(dev.device_kind).name
    prompts, outputs, buf_len = EXECUTED_SHAPES[
        "reduced" if args.reduced else "full"]
    return ExperimentSpec(backend="executed", prompt_range=prompts,
                          output_range=outputs, buf_len=buf_len, **kw)


def main(argv=None) -> RunResult:
    spec = build_spec(argv)
    if spec.backend == "executed":
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    res = spec.run()
    for k, v in res.report.summary().items():
        print(f"{k:22s} {v:.6g}")
    return res


if __name__ == "__main__":
    main()

"""Benchmark harness — one module per paper table/figure.

    fig 1a/1b + fig 4/5  -> benchmarks.precision
    fig 2a/2b + fig 6/7  -> benchmarks.batching
    fig 3a/3b/3c         -> benchmarks.serving
    batch formation      -> benchmarks.formation
    workflows / tasks    -> benchmarks.workflows
    fleet / routing      -> benchmarks.cluster
    geo / autoscale      -> benchmarks.fleet
    closed-loop control  -> benchmarks.control
    §5 scheduling        -> benchmarks.scheduler
    backends / DVFS      -> benchmarks.backend
    §6 macro estimate    -> benchmarks.macro
    simulator perf (ours)-> benchmarks.simperf
    CPU wall-time micro  -> benchmarks.microbench

The paper-figure suites are declarative sweeps over
:class:`repro.ExperimentSpec` (see `repro.sweep`); each prints
``name,us_per_call,derived`` CSV rows whose JSON records carry the
spec's content hash for cross-commit comparability. Claim-check rows
are named ``claim/...`` with pass/fail in the derived column; run.py
exits non-zero if any claim fails.

CLI:
    --list        print available suites and their declarative claims,
                  then exit (runs nothing)
    --only a,b    run only the named benches
    --quick       cheapest configuration (CI smoke): skips the
                  real-compute microbench and shrinks the sweeps
    --json PATH   additionally dump every row as a machine-readable
                  JSON record (one per row; claims carry pass/fail,
                  sweep rows carry their ExperimentSpec hash), so the
                  perf trajectory can be tracked across commits
    --workers N   run cache-miss sweep grid points in an N-process
                  pool (sets REPRO_SWEEP_WORKERS for every suite)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _row_record(suite: str, row) -> dict:
    """One machine-readable record per printed row (claims also carry
    their parsed value and pass/fail verdict; sweep-produced rows carry
    the spec hash of the ExperimentSpec that generated them)."""
    rec = {"suite": suite, "name": row.name,
           "us_per_call": row.us_per_call, "derived": row.derived,
           "spec_hash": getattr(row, "spec_hash", ""),
           "is_claim": row.name.startswith("claim/")}
    if rec["is_claim"]:
        for tok in row.derived.split():
            if tok.startswith("value="):
                try:
                    rec["value"] = float(tok[len("value="):])
                except ValueError:
                    pass
            elif tok.startswith("pass="):
                rec["pass"] = tok[len("pass="):] == "True"
    return rec


def _benches():
    from benchmarks import (backend, batching, cluster, control, fleet,
                            formation, macro, microbench, precision,
                            resilience, scheduler, serving, simperf,
                            workflows)
    return [("precision", precision),
            ("batching", batching),
            ("serving", serving),
            ("formation", formation),
            ("workflows", workflows),
            ("cluster", cluster),
            ("fleet", fleet),
            ("control", control),
            ("resilience", resilience),
            ("scheduler", scheduler),
            ("backend", backend),
            ("macro", macro),
            ("simperf", simperf),
            ("microbench", microbench)]


def _list_suites() -> None:
    """``--list``: the suites and the declarative claims each checks."""
    for name, mod in _benches():
        claims = getattr(mod, "CLAIMS", ())
        print(f"{name}  ({len(claims)} claims)")
        for c in claims:
            thr = (f"({c.threshold[0]}, {c.threshold[1]})"
                   if isinstance(c.threshold, tuple) else c.threshold)
            print(f"  claim/{c.name}  [{c.metric} {c.op} {thr}]")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--list", action="store_true",
                    help="print suites + declarative claims and exit")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names to run")
    ap.add_argument("--quick", action="store_true",
                    help="cheapest/dry configuration for CI smoke")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump all suite rows as JSON records to PATH")
    ap.add_argument("--workers", type=int, default=None, metavar="N",
                    help="run cache-miss sweep points in an N-process "
                         "pool (default: REPRO_SWEEP_WORKERS or 1)")
    args = ap.parse_args(argv)

    if args.workers is not None:
        if args.workers < 1:
            raise SystemExit("--workers must be >= 1")
        os.environ["REPRO_SWEEP_WORKERS"] = str(args.workers)

    if args.quick:
        os.environ.setdefault("REPRO_CLUSTER_NREQ", "80")
        os.environ.setdefault("REPRO_FORMATION_NREQ", "96")
        os.environ.setdefault("REPRO_WORKFLOWS_NREQ", "8")
        os.environ.setdefault("REPRO_SCHED_NREQ", "80")
        os.environ.setdefault("REPRO_BACKEND_NREQ", "48")
        os.environ.setdefault("REPRO_SIMPERF_QUICK", "1")
        os.environ.setdefault("REPRO_MACRO_FLEET_NREQ", "20000")
        os.environ.setdefault("REPRO_FLEET_NREQ", "262144")
        os.environ.setdefault("REPRO_CONTROL_NREQ", "1400")
        os.environ.setdefault("REPRO_RESILIENCE_NREQ", "400")

    if args.list:
        _list_suites()
        return

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    benches = [(n, mod.run) for n, mod in _benches()]
    if args.only:
        want = {w.strip() for w in args.only.split(",")}
        unknown = want - {n for n, _ in benches}
        if unknown:
            raise SystemExit(f"unknown benches: {sorted(unknown)}")
        benches = [(n, fn) for n, fn in benches if n in want]
    elif args.quick:    # an explicit --only selection wins over --quick
        benches = [(n, fn) for n, fn in benches if n != "microbench"]

    print("name,us_per_call,derived")
    failed = []
    records = []
    t_start = time.time()
    for name, fn in benches:
        t0 = time.perf_counter()
        rows = fn()
        for r in rows:
            print(r.csv(), flush=True)
            records.append(_row_record(name, r))
            if r.name.startswith("claim/") and "pass=False" in r.derived:
                failed.append(r.name)
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s",
              flush=True)
    if args.json:
        blob = {"schema": "repro-bench-rows/v2",
                "generated_unix": t_start,
                "quick": bool(args.quick),
                "n_failed_claims": len(failed),
                "records": records}
        with open(args.json, "w") as f:
            json.dump(blob, f, indent=1)
        print(f"# wrote {len(records)} records to {args.json}",
              flush=True)
    if failed:
        print(f"# FAILED claims: {failed}", flush=True)
        sys.exit(1)
    print("# all claims pass", flush=True)


if __name__ == "__main__":
    main()

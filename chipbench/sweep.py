#!/usr/bin/env python3
"""Find an open-loop cell's knee: serve its mix at several fixed rates,
one window each, in one process (set-up and warm-up once).

    python3 chipbench/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 0.8,1.2,1.6 --ttft-ms 1000 --tpot-ms 100

For each rate it prints one JSON line: the share of the window's
requests that met both limits, the TTFT and TPOT percentiles, and the
backlog (the queue wait of the last fifth of the requests against the
first fifth, and the requests still unserved at the close). The knee is
the highest rate at which at least 90% meet both limits and the backlog
does not grow. This is a tool for setting a cell's rate once, not part
of a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import harness, stats, traffic  # noqa: E402


def summarize(rec, ttft_ms: float, tpot_ms: float) -> dict:
    ids = harness.counted_ids(rec)
    ttft = [1e3 * (rec.first[i] - rec.plan[i].due_s)
            for i in ids if i in rec.first]
    tpot = [1e3 * (rec.done[i] - rec.first[i])
            / max(rec.plan[i].max_new_tokens - 1, 1)
            for i in ids if i in rec.done]
    met = sum(1 for i in ids if i in rec.done
              and 1e3 * (rec.first[i] - rec.plan[i].due_s) <= ttft_ms
              and 1e3 * (rec.done[i] - rec.first[i])
              / max(rec.plan[i].max_new_tokens - 1, 1) <= tpot_ms)
    waits = [rec.prefill_start[i] - rec.plan[i].due_s
             for i in ids if i in rec.prefill_start]
    fifth = max(len(waits) // 5, 1)
    unserved_at_close = sum(
        1 for i in ids
        if rec.prefill_start.get(i, float("inf")) > rec.window_s)
    out = {"requests": len(ids), "attainment": met / len(ids),
           "unfinished": len(ids) - len(tpot),
           "unserved_at_close": unserved_at_close,
           "wait_first_fifth_ms": 1e3 * stats.percentile(waits[:fifth], 50),
           "wait_last_fifth_ms": 1e3 * stats.percentile(waits[-fifth:], 50),
           "steps": len(rec.steps), "decode_calls": rec.decode_calls,
           "compiles": rec.compiles}
    for q in (50, 90, 95):
        out[f"ttft_p{q}_ms"] = stats.percentile(ttft, q)
        if tpot:
            out[f"tpot_p{q}_ms"] = stats.percentile(tpot, q)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--ttft-ms", type=float, required=True)
    ap.add_argument("--tpot-ms", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.import_program()
    dev, _ = harness.check_device(cell.chips)
    harness.enable_compile_cache()
    from repro.core.hardware import device_for_kind
    counter = harness.CompileCounter()
    served = harness.build(cell, args.seed, True,
                           device_for_kind(dev.device_kind))
    harness.warm_up(served, cell, args.seed)
    print(json.dumps({"setup_s": time.perf_counter() - T_START}),
          flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        c = dataclasses.replace(cell, settings=dict(cell.settings,
                                                    rate_per_s=rate))
        plan = traffic.generate(c.mix, c.settings, args.seed, args.seconds,
                                served.vocab)
        rec = harness.run_window(served, c, plan, args.seconds, counter)
        print(json.dumps(dict(rate_per_s=rate, **summarize(
            rec, args.ttft_ms, args.tpot_ms))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

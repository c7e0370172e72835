"""90th percentile, over the window's counted requests, of the wait
from when a request was due to the start of the ``stream_step`` that
prefilled it (host clock): time spent in the engine's queue and behind
the decode horizon in flight."""
from chipbench.stats import percentile


def read(ctx):
    rec = ctx.records
    waits = [rec.prefill_start[i] - p.due_s for i, p in enumerate(rec.plan)
             if p.counted and i in rec.prefill_start]
    return 1e3 * percentile(waits, 90) if waits else None

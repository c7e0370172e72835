"""90th percentile, over the window's counted requests, of the time
from when a request was due to its first token on the host (the return
of the ``stream_step`` that prefilled it); a request that never got one
counts as the run's close. The chat cell's TTFT tail, kept here since
its runs spread too widely to carry an end-to-end bound (PERF.md §2)."""
from chipbench.harness import ttfts
from chipbench.stats import percentile


def read(ctx):
    waits = ttfts(ctx.records)
    return 1e3 * percentile(waits, 90) if waits else None

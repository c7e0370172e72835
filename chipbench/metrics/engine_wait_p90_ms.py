"""90th percentile, over the window's counted requests, of the wait
inside the engine: from when the engine took the request
(``Request.t_submit_host``) to the launch of the prefill program that
served it (``t_launch_host``), both on the host clock. None where the
program does not stamp its requests."""
from chipbench.stats import percentile


def read(ctx):
    rec = ctx.records
    waits = []
    for r, p in zip(rec.requests, rec.plan):
        sub = getattr(r, "t_submit_host", -1.0)
        launch = getattr(r, "t_launch_host", -1.0)
        if p.counted and sub >= 0 and launch >= 0:
            waits.append(launch - sub)
    return 1e3 * percentile(waits, 90) if waits else None

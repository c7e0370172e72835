"""Device time of one admission's cache-slot insert: the device runs of
the programs launched inside each ``backend.insert`` span, matched to
their launches by the runtime's run_id, over the spans whose runs are
all in the trace. None where the trace holds no such span."""
from chipbench import spans


def read(ctx):
    t = None if ctx.trace is None else spans.for_cell(ctx.cell.name)
    return None if t is None else spans.cache_insert_ms(t)

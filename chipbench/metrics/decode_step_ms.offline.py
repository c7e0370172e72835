"""Device time per call of the decode program, from the trace (offline
cells: moves tokens/s)."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.program_ms("decode")

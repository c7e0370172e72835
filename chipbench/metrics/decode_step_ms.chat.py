"""Device time per call of the decode program, from the trace (chat
cell: moves the TPOT tail)."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.program_ms("decode")

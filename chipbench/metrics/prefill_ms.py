"""Device time per call of the prefill program, from the trace."""


def read(ctx):
    return None if ctx.trace is None else ctx.trace.program_ms("prefill")

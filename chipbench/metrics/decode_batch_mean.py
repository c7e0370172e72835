"""Output tokens that decode steps produced in the window, over the
decode-program calls the engine counted (``stream_report``): the mean
number of live lanes per decode step."""


def read(ctx):
    rec = ctx.records
    tokens = sum(s.tokens for s in rec.steps if s.prefilled == 0)
    return tokens / rec.decode_calls if rec.decode_calls else None

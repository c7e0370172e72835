"""Host work per decode step that the device waits for, from the
program's spans in the trace (chat cell: moves the TPOT tail): the time
in ``serve.decode`` spans less their ``backend.sync`` spans (the pull
of ids, where the host waits on the device), over the decode launches
inside them. None where the trace holds no program span."""
from chipbench import spans


def read(ctx):
    t = None if ctx.trace is None else spans.for_cell(ctx.cell.name)
    return None if t is None else spans.decode_host_ms(t)

"""Names of the spans the serving path marks in a profiler trace.

Each span is a ``jax.profiler.TraceAnnotation``: while a trace runs
(``jax.profiler.trace(dir)``) it lands on the host plane of the trace,
on the same clock as the device's program runs; when none runs it
costs well under a microsecond and records nothing. Spans nest on the
thread that opens them, so a span's parent is the work that caused it.

==================  =====================================  ==============
span                what it covers                         args
==================  =====================================  ==============
``serve.schedule``  choosing the next prefill batch, or    waiting, live,
                    the decode horizon and its cap         free
``serve.prefill``   one prefill batch, bookkeeping         rows, pad, reqs
                    included
``serve.decode``    one decode horizon (one or more        lanes, steps,
                    steps), bookkeeping included           ran
``backend.cost``    the analytic cost model's pricing of   phase
                    a phase
``backend.launch``  dispatch of a prefill or decode        program, rows
                    program and the eager ops after it
``backend.sync``    the blocking pull of ids to the host   program
``backend.insert``  one admitted request's cache-slot      slot, req
                    insert and feed-token write
==================  =====================================  ==============
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

SCHEDULE = "serve.schedule"
PREFILL = "serve.prefill"
DECODE = "serve.decode"
COST = "backend.cost"
LAUNCH = "backend.launch"
SYNC = "backend.sync"
INSERT = "backend.insert"

ALL = (SCHEDULE, PREFILL, DECODE, COST, LAUNCH, SYNC, INSERT)


def span(name: str, **args) -> TraceAnnotation:
    """A span ``name`` with ``args`` as its trace metadata; more can be
    added inside it with ``set_metadata(**args)``."""
    return TraceAnnotation(name, **args)

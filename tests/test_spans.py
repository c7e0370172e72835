"""The executed serving path's spans and host stamps, on the CPU at the
reduced size: a served run under ``jax.profiler.trace`` holds every
span of ``repro.serving.spans``, nested as that module documents and
with its args; the stamps are set on the executed path and stay -1.0
in the simulator."""
import collections

import jax
import pytest
from jax.profiler import ProfileData

from repro.api import ExperimentSpec
from repro.serving import spans

# two lanes and four requests: a second prefill waits for a free lane
SPEC = dict(model="stablelm-1.6b", reduced=True, fmt="float32",
            n_requests=4, max_batch=2, buf_len=32, prompt_range=(4, 8),
            output_range=(3, 5))

Span = collections.namedtuple("Span", "name start end args parent")


def _program_spans(path):
    """Program spans of the trace's host plane, in start order, each
    with the index of the innermost program span that holds it."""
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = sorted((e for e in line.events if e.name in spans.ALL),
                         key=lambda e: (e.start_ns, -e.duration_ns))
            stack = []
            for e in evs:
                end = e.start_ns + e.duration_ns
                while stack and out[stack[-1]].end <= e.start_ns:
                    stack.pop()
                out.append(Span(e.name, e.start_ns, end, dict(e.stats),
                                stack[-1] if stack else None))
                stack.append(len(out) - 1)
    return out


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(out), profiler_options=opts):
        res = ExperimentSpec(backend="executed", **SPEC).run()
    (path,) = out.rglob("*.xplane.pb")
    return res.report.requests, _program_spans(path)


def test_every_span_is_recorded(served):
    _, sp = served
    assert {s.name for s in sp} == set(spans.ALL)


def test_decode_steps_nest_in_their_horizon(served):
    _, sp = served
    decodes = [i for i, s in enumerate(sp) if s.name == spans.DECODE]
    assert decodes
    for i in decodes:
        kids = collections.Counter(
            (s.name, s.args.get("program", s.args.get("phase")))
            for s in sp if s.parent == i)
        ran = sp[i].args["ran"]
        assert 1 <= ran <= sp[i].args["steps"]
        assert sp[i].args["lanes"] >= 1
        # one pricing, one launch and one pull of ids per step
        assert kids == {(spans.COST, "decode"): ran,
                        (spans.LAUNCH, "decode"): ran,
                        (spans.SYNC, "decode"): ran}
    # every backend span sits in the engine span that caused it
    for s in sp:
        if s.name in (spans.COST, spans.LAUNCH, spans.SYNC, spans.INSERT):
            want = (spans.DECODE if s.args.get("program",
                                               s.args.get("phase"))
                    == "decode" else spans.PREFILL)
            assert sp[s.parent].name == want
        if s.name in (spans.SCHEDULE, spans.PREFILL, spans.DECODE):
            assert s.parent is None


def test_insert_names_its_slot_and_request(served):
    reqs, sp = served
    inserts = [s for s in sp if s.name == spans.INSERT]
    assert sorted(s.args["req"] for s in inserts) == sorted(
        r.req_id for r in reqs)
    assert all(0 <= s.args["slot"] < SPEC["max_batch"] for s in inserts)
    for s in inserts:
        prefill = sp[s.parent]
        assert str(s.args["req"]) in str(prefill.args["reqs"]).split()
        assert prefill.args["rows"] == len(
            str(prefill.args["reqs"]).split())


def test_schedule_reports_the_queue(served):
    _, sp = served
    first = next(s for s in sp if s.name == spans.SCHEDULE)
    assert first.args == {"waiting": SPEC["n_requests"], "live": 0,
                          "free": SPEC["max_batch"]}


def test_executed_path_stamps_submit_and_launch(served):
    reqs, _ = served
    for r in reqs:
        assert 0 <= r.t_submit_host <= r.t_launch_host
    # the last two requests wait for a lane behind the first two
    waits = sorted(r.t_launch_host - r.t_submit_host for r in reqs)
    assert waits[-1] > waits[0]


def test_simulator_leaves_the_stamps_unset():
    reqs = ExperimentSpec(backend="analytic", **SPEC).run().report.requests
    assert reqs and all(r.t_submit_host == -1.0 and r.t_launch_host == -1.0
                        for r in reqs)

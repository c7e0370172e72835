"""The program's spans in a trace (``chipbench/spans.py``) and the three
readers that use them, on traces recorded on one TPU v5e:

- ``data/stablelm-chat-spans.xplane.pb.gz``: a slice of a traced run of
  the chat cell (stablelm-1.6b bf16, 32 lanes, buf 640) with the
  program's spans, from one admission's prefill to the end of the
  decode horizon after it, cut from the run's 4 s trace to the events
  wholly inside that slice;
- ``data/mistral-decode.xplane.pb.gz``: a program from before the
  spans, which gives these readers nothing to read.
"""
import gzip
import importlib.util
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import harness, spans, traffic  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
CHAT = DATA / "stablelm-chat-spans.xplane.pb.gz"
NO_SPANS = DATA / "mistral-decode.xplane.pb.gz"
CELL = "stablelm-1.6b-bf16.chat"
# what the slice's six decode steps and one admission read
DECODE_HOST_MS = 2.1558
CACHE_INSERT_MS = 14.1033


def _reader(name):
    path = ROOT / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _profile(path):
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(gzip.decompress(
        path.read_bytes()))


@pytest.fixture(scope="module")
def chat():
    return spans.from_profile(_profile(CHAT))


@pytest.fixture(scope="module")
def no_spans():
    return spans.from_profile(_profile(NO_SPANS))


def _checkout(tmp_path, data, seed=7):
    """A checkout whose traced run of the chat cell wrote ``data``,
    where the harness puts a trace."""
    d = tmp_path / ".chipbench_out" / f"{CELL}.{seed}.1" / "trace" / \
        "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(gzip.decompress(data.read_bytes()))
    return tmp_path


def _ctx(traced=True):
    return SimpleNamespace(cell=SimpleNamespace(name=CELL),
                           trace=object() if traced else None)


# ---------------------------------------------------------------------------
# spans.py on the recorded chat slice
# ---------------------------------------------------------------------------
def test_every_program_span_is_found(chat):
    names = {s.name for s in chat.spans}
    assert names == {spans.SCHEDULE, spans.PREFILL, spans.DECODE,
                     spans.COST, spans.LAUNCH, spans.SYNC, spans.INSERT}
    for s in chat.spans:
        assert s.start < s.end
        if s.parent is not None:
            p = chat.spans[s.parent]
            assert p.start <= s.start and s.end <= p.end


def test_spans_nest_as_the_program_opens_them(chat):
    for i in chat.named(spans.DECODE):
        kids = [chat.spans[j] for j in chat.children(i)]
        steps = len(spans.decode_launches(chat, i))
        assert steps == chat.spans[i].args["ran"] >= 1
        assert [k.name for k in kids] == [spans.COST, spans.LAUNCH,
                                          spans.SYNC] * steps
    (p,) = chat.named(spans.PREFILL)
    reqs = str(chat.spans[p].args["reqs"]).split()
    inserts = [chat.spans[j] for j in chat.children(p)
               if chat.spans[j].name == spans.INSERT]
    assert [str(s.args["req"]) for s in inserts] == reqs
    assert all(0 <= s.args["slot"] < 32 for s in inserts)


def test_every_launch_finds_its_device_run(chat):
    """Every launch the program's spans made; the slice also holds one
    launch from before its first span, whose run it does not hold."""
    inside = [r for t, r in chat.launches if chat.innermost(t) is not None]
    assert len(inside) == len(chat.launches) - 1 == 56
    assert all(r is not None for r in inside)
    ids = [r.run_id for r in inside]
    assert ids == sorted(ids) and len(set(ids)) == len(ids)


def test_host_and_device_share_a_clock(chat):
    """Each decode launch span opens before its program runs on the
    device, and the pull of its ids ends after the run ends, to within
    a millisecond."""
    n = 0
    for i in chat.named(spans.DECODE):
        kids = chat.children(i)
        for launch, sync in zip(kids[1::3], kids[2::3]):
            (run,) = [r for r in chat.runs_of(launch)
                      if r.program == "jit_decode_step"]
            assert chat.spans[launch].start < run.start
            assert chat.spans[sync].end > run.end - 1e-3
            n += 1
    assert n >= 3


def test_idle_time_falls_under_program_spans(chat):
    idle = spans.idle_by_span(chat)
    total = sum(idle.values())
    assert total > 0
    assert idle.get("none", 0.0) <= 0.1 * total


def test_launches_resolve_by_run_id(no_spans):
    """Every launch in the Mistral trace finds its run by run_id, and
    the run is the program that Python called: that trace was recorded
    with the Python tracer on, so each launch sits in a
    ``PjitFunction(<name>)`` call."""
    assert len(no_spans.launches) == len(no_spans.runs) == 138
    assert all(r is not None for _, r in no_spans.launches)
    calls = []
    for plane in _profile(NO_SPANS).planes:
        for line in plane.lines:
            if line.name == "python":
                calls = [(e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9, e.name)
                         for e in line.events
                         if e.name.startswith("PjitFunction(")]
    called = {"decode_step": "jit_decode_step", "_argmax": "jit__argmax",
              "scatter": "jit_scatter", "concatenate": "jit_concatenate"}
    checked = 0
    for t, run in no_spans.launches:
        inner = max((c for c in calls if c[0] <= t < c[1]),
                    key=lambda c: c[0])
        fn = inner[2][len("PjitFunction("):-1]
        if fn in called:
            assert run.program == called[fn]
            checked += 1
    assert checked == 6 + 6 + 15 + 15


def test_runs_match_launches_in_order_without_run_ids():
    run = [spans.Run("p", s, s + 0.5, None) for s in (1.2, 2.1, 2.2, 9.0)]
    got = spans._in_order([(1.0, None), (2.0, None), (2.05, None),
                           (10.0, None)], run)
    assert [r for _, r in got] == [run[0], run[1], run[2], None]


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------
def test_decode_host_ms(chat, monkeypatch, tmp_path):
    monkeypatch.setattr(spans, "ROOT", _checkout(tmp_path, CHAT))
    got = _reader("decode_host_ms.chat")(_ctx())
    assert got == pytest.approx(DECODE_HOST_MS, abs=1e-3)
    # the same host work, as the decode spans less their pulls
    steps = sum(len(spans.decode_launches(chat, i))
                for i in chat.named(spans.DECODE))
    host = sum(chat.spans[i].end - chat.spans[i].start
               - sum(chat.spans[j].end - chat.spans[j].start
                     for j in chat.children(i)
                     if chat.spans[j].name == spans.SYNC)
               for i in chat.named(spans.DECODE))
    assert got == pytest.approx(1e3 * host / steps)


def test_cache_insert_ms(chat, monkeypatch, tmp_path):
    monkeypatch.setattr(spans, "ROOT", _checkout(tmp_path, CHAT))
    got = _reader("cache_insert_ms")(_ctx())
    assert got == pytest.approx(CACHE_INSERT_MS, abs=1e-3)


def test_span_readers_read_nothing_without_spans(monkeypatch, tmp_path):
    """A program from before the spans, a run without a trace, and
    a checkout with no trace give nothing, and raise nothing."""
    monkeypatch.setattr(spans, "ROOT", _checkout(tmp_path, NO_SPANS))
    for name in ("decode_host_ms.chat", "cache_insert_ms"):
        assert _reader(name)(_ctx()) is None
        assert _reader(name)(_ctx(traced=False)) is None
    monkeypatch.setattr(spans, "ROOT", tmp_path / "empty")
    assert _reader("cache_insert_ms")(_ctx()) is None


def test_for_cell_takes_the_newest_trace(tmp_path):
    root = _checkout(tmp_path, NO_SPANS, seed=1)
    assert not spans.for_cell(CELL, root).spans
    newer = _checkout(tmp_path / "x", CHAT, seed=2) / ".chipbench_out"
    shutil.move(str(newer / f"{CELL}.2.1"),
                str(root / ".chipbench_out" / f"{CELL}.2.1"))
    assert spans.for_cell(CELL, root).spans
    assert spans.for_cell("other.cell", root) is None


def test_engine_wait_p90_ms():
    """p90 over the counted requests of launch - submit, in ms; the
    uncounted request and one the program did not stamp are left out."""
    plan, reqs = [], []
    for i in range(12):
        plan.append(traffic.Planned(0.1 * i, np.zeros(4, np.int32), 2,
                                    i < 11))
        reqs.append(SimpleNamespace(t_submit_host=100.0 + i,
                                    t_launch_host=100.0 + i + 0.001 * i))
    reqs[10] = SimpleNamespace()                     # no stamps at all
    rec = harness.Records(reqs, plan, {}, {}, {}, {}, [], 50.0, 0, 0)
    got = _reader("engine_wait_p90_ms")(SimpleNamespace(records=rec))
    assert got == pytest.approx(np.percentile(np.arange(10.0), 90))
    unstamped = harness.Records([SimpleNamespace() for _ in plan], plan,
                                {}, {}, {}, {}, [], 50.0, 0, 0)
    assert _reader("engine_wait_p90_ms")(
        SimpleNamespace(records=unstamped)) is None

"""The program's own spans in a run's profiler trace, beside the device
runs of the programs they launched.

The executed serving path marks its work with ``TraceAnnotation`` spans
(``serve.*``, ``backend.*``; their names and args are listed in the
program's ``repro/serving/spans.py``). They land on the trace's
``/host:CPU`` plane, on the clock of the device's ``XLA Modules`` runs.
A span's parent is the innermost program span on its thread that holds
it.

A host launch (``PJRT_LoadedExecutable_Execute``) is tied to the device
run it started by the runtime's ``run_id``: a ``DoEnqueueProgram``
event carries it, nested in the launch itself or in the event on
another thread that the launch's flow (``_p``) leads to (``_c``). Where
no launch resolves that way, launches and runs are matched in order.

A reader finds the trace its run just wrote with :func:`for_cell`. A
program without spans, or a run without a trace, gives the readers
nothing to read, never an error.

    python3 chipbench/spans.py <trace.xplane.pb | cell>

prints, as JSON, the three span metrics, the self time per decode step
of each span, and the trace's idle time by the innermost program span
over each gap's middle.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import functools
import glob
import json
import sys
from pathlib import Path
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.trace import _is_container, _union  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
PREFIXES = ("serve.", "backend.")
SCHEDULE, PREFILL, DECODE = "serve.schedule", "serve.prefill", "serve.decode"
COST, LAUNCH, SYNC = "backend.cost", "backend.launch", "backend.sync"
INSERT = "backend.insert"
HOST_LAUNCH = "PJRT_LoadedExecutable_Execute"
ENQUEUE = "DoEnqueueProgram"


@dataclasses.dataclass
class Span:
    name: str
    start: float            # seconds, on the trace's clock
    end: float
    args: Dict[str, object]
    parent: Optional[int]   # index of the enclosing program span


@dataclasses.dataclass
class Run:
    """One device run of a program (``XLA Modules`` on the first
    device)."""
    program: str            # ``jit_decode_step``
    start: float
    end: float
    run_id: Optional[int]


@dataclasses.dataclass
class Trace:
    spans: List[Span]                 # in start order
    runs: List[Run]                   # in start order
    # each host launch: its start, and the device run it started (None
    # where that run is not in the trace)
    launches: List[Tuple[float, Optional[Run]]]
    busy: List[Tuple[float, float]]   # union of the first device's ops

    def __post_init__(self):
        self._kids: Dict[int, List[int]] = collections.defaultdict(list)
        for j, s in enumerate(self.spans):
            if s.parent is not None:
                self._kids[s.parent].append(j)
        self._starts = [s.start for s in self.spans]
        self._launch_starts = [t for t, _ in self.launches]

    def named(self, name: str) -> List[int]:
        return [i for i, s in enumerate(self.spans) if s.name == name]

    def children(self, i: int) -> List[int]:
        return self._kids.get(i, [])

    def within(self, i: int) -> List[int]:
        """Every program span nested in span ``i``."""
        out, todo = [], [i]
        while todo:
            kids = self.children(todo.pop())
            out += kids
            todo += kids
        return out

    def self_s(self, i: int) -> float:
        """Span ``i`` less the time in its child spans."""
        s = self.spans[i]
        return (s.end - s.start) - sum(self.spans[j].end - self.spans[j].start
                                       for j in self.children(i))

    def runs_of(self, i: int) -> Optional[List[Run]]:
        """The device runs of the programs launched inside span ``i``;
        None if any of them is not in the trace."""
        s = self.spans[i]
        lo = bisect.bisect_left(self._launch_starts, s.start)
        hi = bisect.bisect_left(self._launch_starts, s.end)
        runs = [r for _, r in self.launches[lo:hi]]
        return None if any(r is None for r in runs) else runs

    def innermost(self, at: float) -> Optional[int]:
        """The deepest program span open at time ``at``: the last span
        to start by then, or the first of its ancestors still open."""
        j = bisect.bisect_right(self._starts, at) - 1
        while j >= 0 and self.spans[j].end <= at:
            j = self.spans[j].parent
            if j is None:
                return None
        return j if j >= 0 else None


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------
def _nest(events):
    """(event, enclosing events on the same line) in start order."""
    stack: List = []
    for ev in sorted(events, key=lambda e: (e.start_ns, -e.duration_ns)):
        while stack and stack[-1].start_ns + stack[-1].duration_ns \
                <= ev.start_ns:
            stack.pop()
        yield ev, list(stack)
        stack.append(ev)


def _host(plane):
    """Program spans, and each launch's start and run_id, from the host
    plane's lines."""
    spans: List[Tuple[float, float, str, Dict, Optional[int]]] = []
    starts: List[float] = []            # one per launch
    flows: List[List[int]] = []         # the flows each launch starts
    direct: Dict[int, int] = {}         # launch -> run_id nested in it
    ctx_run: Dict[int, int] = {}        # flow -> run_id nested in it
    for ln in plane.lines:
        tag: Dict[int, Tuple[str, int]] = {}   # id(event) -> (kind, index)
        for ev, above in _nest(ln.events):
            name = ev.name
            if name.startswith(PREFIXES):
                parent = next((tag[id(a)][1] for a in reversed(above)
                               if tag.get(id(a), ("",))[0] == "span"), None)
                tag[id(ev)] = ("span", len(spans))
                spans.append((ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9, name,
                              dict(ev.stats), parent))
                continue
            launch = next((tag[id(a)][1] for a in above
                           if tag.get(id(a), ("",))[0] == "launch"), None)
            if name == HOST_LAUNCH and launch is None:
                launch = len(starts)
                tag[id(ev)] = ("launch", launch)
                starts.append(ev.start_ns * 1e-9)
                flows.append([])
            if launch is None and name != ENQUEUE:
                continue
            stats = dict(ev.stats)
            if launch is not None and "_p" in stats:
                flows[launch].append(stats["_p"])
            if name == ENQUEUE and "run_id" in stats:
                if launch is not None:
                    direct.setdefault(launch, stats["run_id"])
                for a in above:
                    c = dict(a.stats).get("_c")
                    if c is not None:
                        ctx_run[c] = stats["run_id"]
    ids = [direct.get(k, next((ctx_run[p] for p in flows[k] if p in ctx_run),
                              None))
           for k in range(len(starts))]
    return spans, list(zip(starts, ids))


def from_profile(pd) -> Trace:
    """The program's spans and the device's runs in a loaded
    ``ProfileData``."""
    raw_spans, raw_launches, runs, busy = [], [], [], []
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            s, la = _host(plane)
            raw_spans += s
            raw_launches += la
        elif plane.name == "/device:TPU:0":
            for ln in plane.lines:
                if ln.name == "XLA Modules":
                    for ev in ln.events:
                        st = dict(ev.stats)
                        runs.append(Run(ev.name.split("(")[0],
                                        ev.start_ns * 1e-9,
                                        (ev.start_ns + ev.duration_ns) * 1e-9,
                                        st.get("run_id")))
                elif ln.name == "XLA Ops":
                    busy += [(ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
                             for ev in ln.events
                             if ev.duration_ns > 0
                             and not _is_container(ev.name)]
    runs.sort(key=lambda r: r.start)
    # spans: renumber into start order, keeping each parent
    order = sorted(range(len(raw_spans)), key=lambda i: raw_spans[i][0])
    new = {old: k for k, old in enumerate(order)}
    spans = [Span(n, s, e, a, None if p is None else new[p])
             for s, e, n, a, p in (raw_spans[i] for i in order)]
    raw_launches.sort(key=lambda la: la[0])
    by_id = {r.run_id: r for r in runs if r.run_id is not None}
    if any(rid in by_id for _, rid in raw_launches):
        launches = [(t, by_id.get(rid)) for t, rid in raw_launches]
    else:
        launches = _in_order(raw_launches, runs)
    return Trace(spans, runs, launches, _union(busy))


def _in_order(raw_launches, runs):
    """Each launch's run, with no run_id to go by: the first run not yet
    taken that starts after the launch does."""
    out, k = [], 0
    for t, _ in raw_launches:
        while k < len(runs) and runs[k].start < t:
            k += 1
        out.append((t, runs[k] if k < len(runs) else None))
        k += k < len(runs)
    return out


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime_ns: int) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def load(path: Path) -> Trace:
    """The trace at ``path``, read once while the file is unchanged."""
    path = Path(path)
    return _load(str(path), path.stat().st_mtime_ns)


def for_cell(cell: str, root: Optional[Path] = None) -> Optional[Trace]:
    """The newest trace a traced run of ``cell`` wrote under ``root``
    (the checkout; ``.chipbench_out/<cell>.<seed>.1/trace/``), or
    None."""
    pattern = f"{glob.escape(cell)}.*.1/trace/**/*.xplane.pb"
    files = sorted((Path(root or ROOT) / ".chipbench_out").glob(pattern),
                   key=lambda p: p.stat().st_mtime_ns)
    return load(files[-1]) if files else None


# ---------------------------------------------------------------------------
# what the readers and the report compute
# ---------------------------------------------------------------------------
def decode_launches(t: Trace, i: int) -> List[int]:
    return [j for j in t.within(i) if t.spans[j].name == LAUNCH
            and t.spans[j].args.get("program") == "decode"]


def decode_host_ms(t: Trace) -> Optional[float]:
    """Host work per decode step that the device waits for: the time in
    ``serve.decode`` spans less their ``backend.sync`` spans (the own
    time of every span in them but the pulls), over the decode launches
    inside them."""
    own = self_ms_per_step(t)
    return sum(v for k, v in own.items() if k != SYNC) if own else None


def cache_insert_ms(t: Trace) -> Optional[float]:
    """Device time of the programs launched inside each
    ``backend.insert`` span, over the spans that launched some and
    whose runs are all in the trace."""
    total, n = 0.0, 0
    for i in t.named(INSERT):
        runs = t.runs_of(i)
        if runs:
            total += sum(r.end - r.start for r in runs)
            n += 1
    return 1e3 * total / n if n else None


def idle_by_span(t: Trace) -> Dict[str, float]:
    """Seconds of each gap between the first device's operations, by
    the innermost program span over the gap's middle (``none`` where no
    program span is open)."""
    out: Dict[str, float] = collections.defaultdict(float)
    for (_, e0), (s1, _) in zip(t.busy, t.busy[1:]):
        i = t.innermost(0.5 * (e0 + s1))
        out["none" if i is None else t.spans[i].name] += s1 - e0
    return dict(out)


def self_ms_per_step(t: Trace) -> Dict[str, float]:
    """Self time of each span name inside ``serve.decode`` spans (the
    ``serve.decode`` spans included), per decode launch."""
    steps = 0
    own: Dict[str, float] = collections.defaultdict(float)
    for i in t.named(DECODE):
        steps += len(decode_launches(t, i))
        for j in [i] + t.within(i):
            own[t.spans[j].name] += t.self_s(j)
    return {k: 1e3 * v / steps for k, v in own.items()} if steps else {}


def report(t: Trace) -> Dict:
    idle = idle_by_span(t)
    total = sum(idle.values())
    return {
        "decode_host_ms": decode_host_ms(t),
        "cache_insert_ms": cache_insert_ms(t),
        "decode_steps": sum(len(decode_launches(t, i))
                            for i in t.named(DECODE)),
        "inserts": len(t.named(INSERT)),
        "self_ms_per_step": self_ms_per_step(t),
        "idle_ms_by_span": {k: 1e3 * v for k, v in
                            sorted(idle.items(), key=lambda kv: -kv[1])},
        "idle_ms": 1e3 * total,
        "idle_under_program_span": (1.0 - idle.get("none", 0.0) / total
                                    if total else None),
        "runs_by_program": dict(collections.Counter(r.program
                                                    for r in t.runs)),
        "launches_resolved": sum(r is not None for _, r in t.launches),
        "launches": len(t.launches),
    }


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print("usage: python3 chipbench/spans.py <trace.xplane.pb | cell>",
              file=sys.stderr)
        return 2
    path = Path(args[0])
    t = load(path) if path.is_file() else for_cell(args[0])
    if t is None:
        print(f"no trace for {args[0]!r}", file=sys.stderr)
        return 1
    print(json.dumps(report(t), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

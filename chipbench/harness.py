"""One run of one cell: set-up, the open-loop window, the check.

The cell is found by name in ``BENCHMARK.json``; its parts are files
found by name: the configuration ``configs/<config>.json``, the
architecture it names ``architectures/<architecture>.py`` (the
program's ``ModelConfig``, the plain reference and the model FLOPs),
the mix ``traffic/<traffic>.json``, the engine settings and rate
``cells/<cell>.json``, and one reader per per-layer metric
``metrics/<metric>.py``. Adding any of them, an architecture included,
takes new files and entries, and no edit here.

The system under test is driven only through its public calls:
``build_model``, ``Model.quantize``, ``ServeEngine(execute=True)`` and
its ``stream_start / stream_submit / stream_can_step / stream_step /
stream_report``. Each ``stream_step`` runs one prefill batch or one
decode horizon (up to the next completion) and returns once its ids are
on the host, so the harness stamps tokens on the host clock when it
returns.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Optional

import numpy as np

from chipbench import stats, traffic
from chipbench.peaks import peaks_for

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
# requests due in the window get this long after its close to finish
DRAIN_LIMIT_S = 60.0


class Refused(RuntimeError):
    """The run cannot measure anything: no chip, an unknown chip, a
    cell that does not exist, or a configuration that names no
    architecture there is a file for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    settings: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    # the module of architectures/<architecture>.py: model_config,
    # logits_at, prefill_flops, decode_flops
    arch: ModuleType


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _module(path: Path, name: str) -> ModuleType:
    """The Python file at ``path``, loaded as module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def architecture(config: Dict, root: Path = ROOT) -> ModuleType:
    """The architecture module the configuration names under
    ``architecture_module``, ``chipbench/architectures/<module>.py``.
    There is no default. (The key is not Hugging Face's
    ``architectures``, which names the published class.)"""
    name = config.get("architecture_module")
    if not isinstance(name, str) or not name:
        raise Refused(f"configuration {config.get('name')!r} names no "
                      f"architecture module")
    path = root / "chipbench" / "architectures" / f"{name}.py"
    if not path.is_file():
        raise Refused(f"configuration {config.get('name')!r} names "
                      f"architecture module {name!r}, and there is no "
                      f"{path}")
    return _module(path, "chipbench_arch_" + name.replace(".", "_"))


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Everything a run of cell ``name`` needs, from files found by
    name."""
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    wl = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / configs[wl["config"]]["file"])
    mix = _json(root / "chipbench" / "traffic" / f"{wl['traffic']}.json")
    settings = _json(root / "chipbench" / "cells" / f"{name}.json")

    def applies(metric):
        return name in metric.get("workloads", [name])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name, int(wl["chips"]), config, mix, settings, e2e,
                per_layer, architecture(config, root))


# ---------------------------------------------------------------------------
# device and program
# ---------------------------------------------------------------------------
def check_device(chips: int):
    """JAX's devices, refusing anything but enough TPUs of a kind the
    peaks table knows."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is {dev.platform!r} "
                      f"({dev.device_kind})")
    peaks_for(dev.device_kind)
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees "
                      f"{len(devs)}")
    return dev, len(devs)


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in this
    checkout, caching every program, however fast it compiled."""
    import jax
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def import_program(root: Path = ROOT) -> None:
    src = root / "src"
    if not (src / "repro").is_dir():
        raise Refused(f"no program at {src / 'repro'}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


class CompileCounter:
    """Compilations and persistent-cache loads, from JAX's own
    monitoring events. Either one inside the window is a program the
    set-up did not warm."""

    def __init__(self) -> None:
        import jax
        self.count = 0

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@dataclasses.dataclass
class Served:
    """The system under test, built and warmed, with what the check
    needs to regenerate its weights."""
    engine: object
    layout: Dict
    key: object
    vocab: int


def build(cell: Cell, seed: int, on_tpu: bool, device_spec) -> Served:
    """Model, seeded weights and engine, through the program's public
    calls (those ``ExperimentSpec.build_engine`` makes)."""
    import jax
    from repro.batching.policy import make_batch_policy
    from repro.models import build_model
    from repro.serving.engine import ServeEngine
    from chipbench import weights

    cfg = cell.arch.model_config(cell.config)
    prec = cell.config["precision"]
    model = build_model(cfg, prec["fmt"], use_pallas_kernels=on_tpu)
    if model.policy.is_quantized and prec["fmt"] == "int8":
        if model.policy.outlier_fraction != prec["outlier_fraction"]:
            raise Refused(
                f"the program's int8 outlier fraction is "
                f"{model.policy.outlier_fraction}, the configuration "
                f"states {prec['outlier_fraction']}")
    key = weights.seed_key(seed)
    lay = weights.layout(model)
    params = model.quantize(weights.make(model, key))
    jax.block_until_ready(params)
    s = cell.settings
    policy = make_batch_policy(s["policy"], max_batch=s["max_batch"],
                               max_prefill_batch=s["max_prefill_batch"])
    engine = ServeEngine(cfg, fmt=prec["fmt"], device=device_spec,
                         batch_policy=policy, execute=True, model=model,
                         params=params, buf_len=s["buf_len"])
    return Served(engine, lay, key, cfg.vocab_size)


def _request(i: int, prompt: np.ndarray, max_new: int, due: float = 0.0):
    from repro.serving.requests import Request
    return Request(req_id=i, prompt=prompt, prompt_len=len(prompt),
                   max_new_tokens=max_new, arrival_time=due)


def warm_up(served: Served, cell: Cell, seed: int) -> None:
    """Run every shape the window can reach, through the stream API:
    a full batch (every cache lane), then each (prefill rows n <=
    max_prefill_batch, pad) pair the mix's prompt lengths give under
    the program's pad-to-a-multiple-of-8 rule, each with one decode
    step."""
    eng = served.engine
    s = cell.settings
    rng = np.random.default_rng(int(seed) + 1)
    pads = sorted({min(-(-p // 8) * 8, s["buf_len"])
                   for p in traffic.prompt_support(cell.mix)})
    rid = [0]

    def serve(lengths):
        for n in lengths:
            rid[0] += 1
            eng.stream_submit(_request(
                -rid[0], rng.integers(0, served.vocab, n).astype(np.int32),
                2))
        while eng.stream_can_step():
            eng.stream_step()

    eng.stream_start()
    serve([pads[0]] * s["max_batch"])
    for pad in pads:
        for n in range(1, s["max_prefill_batch"] + 1):
            serve([pad] * n)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Step:
    start: float
    end: float
    prefilled: int          # requests that got their first token
    tokens: int             # output tokens that reached the host


@dataclasses.dataclass
class Records:
    """What the window saw, on the host clock (seconds from the
    window's start)."""
    requests: List            # program Request objects, in due order
    plan: List                # traffic.Planned, same order
    submitted: Dict[int, float]
    prefill_start: Dict[int, float]
    first: Dict[int, float]
    done: Dict[int, float]
    steps: List[Step]
    window_s: float
    decode_calls: int
    compiles: int
    closed_s: float = 0.0     # when the loop ended


class _Tracer:
    """The profiler over a slice of the window: from the first step end
    past ``at_s`` until ``length_s`` later. ``start_s`` and ``stop_s``
    are how long starting and stopping it held the host loop."""

    def __init__(self, at_s: float, length_s: float, out_dir: Path):
        self.at, self.length, self.dir = at_s, length_s, out_dir
        self.t0 = self.t1 = None
        self.start_s = self.stop_s = 0.0

    def poll(self, now: float, clock) -> None:
        import jax
        if self.t0 is None and now >= self.at:
            shutil.rmtree(self.dir, ignore_errors=True)
            # harness spans and device events only: tracing every Python
            # call would slow the host loop it measures
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.t0 = clock()
            self.start_s = self.t0 - now
        elif (self.t0 is not None and self.t1 is None
              and now >= self.t0 + self.length):
            self.close(clock)

    def close(self, clock) -> None:
        import jax
        if self.t0 is not None and self.t1 is None:
            self.t1 = clock()
            jax.profiler.stop_trace()
            self.stop_s = clock() - self.t1


def run_window(served: Served, cell: Cell, plan, seconds: float,
               counter: CompileCounter, tracer: Optional[_Tracer] = None
               ) -> Records:
    """Serve ``plan`` open loop on the host clock.

    Every request is submitted once it is due; ``stream_step`` runs
    while the engine can step; otherwise the loop sleeps until the next
    request is due. A request's first token is stamped when the step
    that prefilled it returns, its last when the step that completed it
    returns.

    Open loop (``poisson``): the window is ``[0, seconds)`` of due
    times. Requests due before it fill the batch and requests due after
    it keep the load on; neither is counted. The run goes on until
    every counted request has finished, or ``DRAIN_LIMIT_S`` after the
    close. Offline (``all_at_once``): the window ends with the first
    step that returns at or after ``seconds``; its tokens count.
    """
    import jax
    from repro.serving.requests import RequestStatus
    eng = served.engine
    offline = cell.mix["arrival"] == "all_at_once"
    reqs = [_request(i, p.prompt, p.max_new_tokens, p.due_s)
            for i, p in enumerate(plan)]
    counted = [r for r, p in zip(reqs, plan) if p.counted]
    rec = Records(reqs, plan, {}, {}, {}, {}, [], 0.0, 0, 0)
    eng.stream_start()
    decode0 = eng.stream_report().n_decode_steps
    c0 = counter.count
    origin = time.perf_counter() - plan[0].due_s

    def clock():
        return time.perf_counter() - origin

    waiting: List = []       # submitted, no first token yet
    live: List = []          # first token, not finished
    n_counted_done = 0
    i, n = 0, len(reqs)
    ann = jax.profiler.TraceAnnotation
    while True:
        now = clock()
        if tracer is not None:
            tracer.poll(now, clock)
        if i < n and plan[i].due_s <= now:
            with ann("bench.submit"):
                while i < n and plan[i].due_s <= now:
                    eng.stream_submit(reqs[i])
                    rec.submitted[i] = clock()
                    waiting.append(reqs[i])
                    i += 1
        if offline:
            if rec.steps and rec.steps[-1].end >= seconds:
                break
        elif n_counted_done == len(counted) and now >= seconds:
            break
        if now > seconds + DRAIN_LIMIT_S:
            break
        if eng.stream_can_step():
            before = sum(len(r.generated) for r in live)
            start = clock()
            with ann("bench.stream_step"):
                eng.stream_step()
            end = clock()
            got = [r for r in waiting if r.generated]
            if got:
                waiting = [r for r in waiting if not r.generated]
                for r in got:
                    rec.prefill_start[r.req_id] = start
                    rec.first[r.req_id] = end
                live += got
            tokens = sum(len(r.generated) for r in live) - before
            fin = [r for r in live if r.status is RequestStatus.DONE]
            if fin:
                live = [r for r in live if r.status is not RequestStatus.DONE]
                for r in fin:
                    rec.done[r.req_id] = end
                    n_counted_done += plan[r.req_id].counted
            rec.steps.append(Step(start, end, len(got), tokens))
        elif i < n:
            with ann("bench.sleep"):
                time.sleep(max(0.0, plan[i].due_s - clock()))
        else:
            break
    rec.closed_s = clock()
    if tracer is not None:
        tracer.close(clock)
    rec.compiles = counter.count - c0
    rec.decode_calls = eng.stream_report().n_decode_steps - decode0
    rec.window_s = rec.steps[-1].end if offline else float(seconds)
    return rec


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------
def counted_ids(rec: Records) -> List[int]:
    return [i for i, p in enumerate(rec.plan) if p.counted]


def ttfts(rec: Records) -> List[float]:
    """Seconds from due to first token of each counted request; one
    that never got its first token counts as the run's close."""
    return [rec.first.get(i, rec.closed_s) - rec.plan[i].due_s
            for i in counted_ids(rec)]


def end_to_end(cell: Cell, rec: Records) -> Dict[str, float]:
    """Every end-to-end metric this cell reports, but ``setup_s``. A
    request that never got its first or last token is stamped when the
    run ended: it counts as late as the run can show."""
    ids = counted_ids(rec)
    out: Dict[str, float] = {}
    names = {m["name"] for m in cell.end_to_end}
    first = {i: rec.first.get(i, rec.closed_s) for i in ids}
    done = {i: rec.done.get(i, rec.closed_s) for i in ids}
    if "ttft_p90_ms" in names:
        out["ttft_p90_ms"] = 1e3 * stats.percentile(ttfts(rec), 90)
    if "tpot_p90_ms" in names:
        out["tpot_p90_ms"] = 1e3 * stats.percentile(
            [(done[i] - first[i])
             / max(rec.plan[i].max_new_tokens - 1, 1) for i in ids], 90)
    if "tokens_per_s" in names:
        out["tokens_per_s"] = (sum(s.tokens for s in rec.steps)
                               / rec.window_s)
    return out


def unfinished(cell: Cell, rec: Records) -> int:
    """Requests the window owes an answer to and never gave one:
    offline, none (the window ends while the queue is full); open loop,
    every counted request not finished by the drain limit."""
    if cell.mix["arrival"] == "all_at_once":
        return 0
    return sum(1 for i in counted_ids(rec) if i not in rec.done)


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def check_sample(rec: Records, seed: int, n: int) -> List[int]:
    """Finished requests the reference recomputes: the one with most
    served tokens, and ``n`` more drawn from the seed."""
    done = [i for i in rec.done if rec.plan[i].counted]
    if not done:
        return []
    longest = max(done, key=lambda i: (len(rec.requests[i].generated), -i))
    rest = sorted(set(done) - {longest})
    rng = np.random.default_rng(int(seed) + 2)
    k = min(n, len(rest))
    return [longest] + sorted(rng.choice(rest, size=k, replace=False)
                              .tolist() if k else [])


def sample_rows(rec: Records, sample: List[int], vocab: int):
    """The reference's input for each sampled request (its prompt and
    served tokens but the last), the position that chose its first
    served token, its served tokens, and the count of served tokens
    missing or outside the vocabulary."""
    from chipbench import reference
    rows, firsts, served, bad = [], [], [], 0
    for i in sample:
        r = rec.requests[i]
        got = [int(t) for t in r.generated]
        bad += ((len(got) != r.max_new_tokens)
                + sum(not 0 <= t < vocab for t in got))
        row, first = reference.served_rows(r.prompt, got)
        rows.append(row)
        firsts.append(first)
        served.append(np.asarray(got, np.int32))
    return rows, firsts, served, bad


def gap_stats(cell: Cell, layout: Dict, key, rows, firsts,
              candidates) -> List[Dict[str, float]]:
    """For each column of ``candidates`` (one (positions, T) array of
    token ids per row): by how much the reference's logit of that token
    lies below the reference's best at its position, as the widest gap
    over every checked position, the mean gap, and the share of
    positions where the token is not the reference's first choice."""
    allg = np.concatenate(
        [mx[:, None] - lt for mx, lt in cell.arch.logits_at(
            cell.config, layout, key, rows, candidates, firsts)], axis=0)
    return [{"widest": float(allg[:, j].max()),
             "mean": float(allg[:, j].mean()),
             "off_first": float((allg[:, j] > 0).mean())}
            for j in range(allg.shape[1])]


def served_gaps(cell: Cell, layout: Dict, key, vocab: int, rec: Records,
                sample: List[int]) -> Dict[str, float]:
    """By how much the sample's served tokens lie below the reference's
    best logit (mean and widest gap), and the served tokens that are
    missing or out of range."""
    rows, firsts, served, bad = sample_rows(rec, sample, vocab)
    g = gap_stats(cell, layout, key, rows, firsts,
                  [t[:, None] for t in served])[0]
    return {"widest_gap": g["widest"], "mean_gap": g["mean"],
            "off_first": g["off_first"], "bad_tokens": float(bad),
            "tokens_checked": float(sum(len(t) for t in served))}


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read."""
    cell: Cell
    records: Records
    peaks: Dict[str, float]
    trace: Optional[object]      # chipbench.trace.Reduced, or None


def read_metric(name: str, ctx: Context, root: Path = ROOT
                ) -> Optional[float]:
    """The value of per-layer metric ``name``, from its reader
    ``chipbench/metrics/<name>.py``; None where it found nothing."""
    mod = _module(root / "chipbench" / "metrics" / f"{name}.py",
                  "chipbench_metric_" + name.replace(".", "_"))
    value = mod.read(ctx)
    return None if value is None else float(value)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        dev, n_devices: int, out_dir: Path) -> Dict:
    """One run of ``cell``, after the device check; returns the result
    line's object. ``t_start`` is the process's start on the host
    clock: set-up runs from it to the window's first request."""
    from repro.core.hardware import device_for_kind, get_device
    from chipbench import trace as tracing

    on_tpu = dev.platform == "tpu"
    counter = CompileCounter()
    spec = (device_for_kind(dev.device_kind) if on_tpu
            else get_device("tpu-v5e"))
    served = build(cell, seed, on_tpu, spec)
    warm_up(served, cell, seed)
    plan = traffic.generate(cell.mix, cell.settings, seed, seconds,
                            served.vocab)
    tracer = None
    if trace:
        at, length = tracing.slice_of(seconds)
        tracer = _Tracer(at, length, out_dir / "trace")
    setup_s = time.perf_counter() - t_start
    rec = run_window(served, cell, plan, seconds, counter, tracer)
    stats_ = dev.memory_stats() or {}
    peak = int(stats_.get("peak_bytes_in_use", 0))
    # what the served state holds at the close, beside the process's
    # peak (which set-up may set)
    in_use = int(stats_.get("bytes_in_use", 0))
    reduced = None
    if tracer is not None and tracer.t0 is not None:
        reduced = tracing.reduce_dir(tracer.dir, tracer.t1 - tracer.t0)
    ctx = Context(cell, rec, peaks_for(dev.device_kind) if on_tpu
                  else peaks_for("TPU v5 lite"), reduced)
    e2e = end_to_end(cell, rec)
    layer_values = {}
    if trace:
        for m in cell.per_layer:
            v = read_metric(m["name"], ctx)
            if v is not None:
                layer_values[m["name"]] = v

    sample = check_sample(rec, seed, cell.settings["check_requests"])
    layout, key, vocab = served.layout, served.key, served.vocab
    served.engine = None
    del served
    gc.collect()
    checks = served_gaps(cell, layout, key, vocab, rec, sample)
    checks["unfinished"] = float(unfinished(cell, rec))
    limits = {"mean_gap": cell.settings["limits"]["mean_gap"],
              "bad_tokens": 0.0, "unfinished": 0.0}
    correct = (bool(sample) and checks["tokens_checked"] > 0
               and all(checks[k] <= limits[k] for k in limits))

    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if trace:
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in layer_values.items()}
    else:
        values = dict(e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    ids = counted_ids(rec)
    late = [rec.submitted[i] - rec.plan[i].due_s for i in rec.submitted]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_devices, "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(ids),
           "failed": int(checks["unfinished"]), "metrics": metrics,
           "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = reduced.breakdown
    out["run"] = {
        "seed": seed, "seconds": seconds, "setup_s": setup_s,
        "window_s": rec.window_s, "steps": len(rec.steps),
        "decode_calls": rec.decode_calls, "compiles_in_window": rec.compiles,
        "memory_in_use_bytes": in_use,
        "generator_late_p50_ms": 1e3 * stats.percentile(late, 50),
        "generator_late_max_ms": 1e3 * max(late),
        "end_to_end": e2e,
    }
    if tracer is not None:
        out["run"]["trace_start_s"] = tracer.start_s
        out["run"]["trace_stop_s"] = tracer.stop_s
    out["run"]["tokens_checked"] = int(checks["tokens_checked"])
    out["run"]["widest_gap"] = checks["widest_gap"]
    out["run"]["off_first"] = checks["off_first"]
    out["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                     for k in limits}
    return out

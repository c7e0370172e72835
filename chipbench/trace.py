"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

What the TPU's trace holds, as read with ``jax.profiler.ProfileData``:
a plane ``/device:TPU:<i>`` per chip, whose line ``XLA Modules`` has one
event per program run (``jit_decode_step(<hash>)``) and whose line
``XLA Ops`` has one event per operation, named by its HLO text
(``%closed_call.51 = bf16[64,14336]{...} custom-call(bf16[64,4096]
..., s8[4096,14336] ..., f32[1,14336] ...),
custom_call_target="tpu_custom_call"``); and a plane ``/host:CPU``
whose line ``python`` holds the harness's own ``TraceAnnotation`` spans
(``bench.submit``, ``bench.stream_step``, ``bench.sleep``). Host and
device events share one clock to about a millisecond.

Busy time is the union of the operations' intervals; an idle gap is a
hole in that union, labelled by the harness span that covers its
middle.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

# program name in the trace, by the role the harness knows it in
PROGRAMS = {"prefill": "jit__lambda", "decode": "jit_decode_step"}
# the int8 quant_matmul kernel: a Pallas custom call whose operands are
# bf16 activations (M, K) and int8 codes (K, N)
_INT8_KERNEL = re.compile(
    r"custom-call\(bf16\[(\d+),(\d+)\][^ ]* [^,]+, s8\[(\d+),(\d+)\]")
# operations that contain others (the scan over layers) are not
# device work of their own
_CONTAINERS = ("while", "conditional", "call")
TOP = 10


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    programs: Dict[str, List[float]]      # seconds per run, by program
    int8_kernel: List[Tuple[int, int, int, float]]   # (M, K, N, seconds)
    breakdown: Dict[str, List]

    def program_ms(self, role: str) -> Optional[float]:
        """Mean device time of one run of the program in ``role``."""
        runs = self.programs.get(PROGRAMS[role], [])
        return 1e3 * sum(runs) / len(runs) if runs else None


def slice_of(seconds: float) -> Tuple[float, float]:
    """(start, length) of the traced slice of a window of ``seconds``:
    four seconds from 40% in, or a fifth of a shorter window."""
    return 0.4 * seconds, min(4.0, 0.2 * seconds)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _op_name(text: str) -> str:
    """``copy.47 bf16[1,32,640,32,64]`` from an operation's HLO text."""
    head, _, rest = text.partition(" = ")
    shape = rest.split("{")[0].split(" ")[0]
    return f"{head.lstrip('%')} {shape}".strip()


def _is_container(text: str) -> bool:
    name = text.lstrip("%").split(" ")[0].split(".")[0]
    return name in _CONTAINERS


def reduce_profile(pd, window_s: float) -> Reduced:
    """Reduce a loaded ``ProfileData``."""
    chips = [p for p in pd.planes if re.fullmatch(r"/device:TPU:\d+", p.name)]
    if not chips:
        raise ValueError("the trace has no TPU device plane")
    busy_total = 0.0
    programs: Dict[str, List[float]] = collections.defaultdict(list)
    kernel: List[Tuple[int, int, int, float]] = []
    op_time: Dict[str, float] = collections.defaultdict(float)
    busy0: List[Tuple[float, float]] = []
    for i, plane in enumerate(chips):
        lines = {ln.name: ln for ln in plane.lines}
        intervals = []
        for ev in lines["XLA Ops"].events if "XLA Ops" in lines else ():
            text, dur = ev.name, ev.duration_ns * 1e-9
            if dur <= 0 or _is_container(text):
                continue
            start = ev.start_ns * 1e-9
            intervals.append((start, start + dur))
            op_time[_op_name(text)] += dur
            if 'custom_call_target="tpu_custom_call"' in text:
                m = _INT8_KERNEL.search(text)
                if m and m.group(2) == m.group(3):
                    kernel.append((int(m.group(1)), int(m.group(2)),
                                   int(m.group(4)), dur))
        merged = _union(intervals)
        busy_total += sum(e - s for s, e in merged)
        if i == 0:
            busy0 = merged
        if "XLA Modules" in lines:
            for ev in lines["XLA Modules"].events:
                programs[ev.name.split("(")[0]].append(ev.duration_ns * 1e-9)
    spans = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            spans += [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns)
                       * 1e-9, ev.name) for ev in ln.events
                      if ev.name.startswith("bench.")]
    gaps = []
    for (_, e0), (s1, _) in zip(busy0, busy0[1:]):
        mid = 0.5 * (e0 + s1)
        label = next((n for s, e, n in spans if s <= mid < e), "host other")
        gaps.append((f"idle in {label}", s1 - e0))
    gaps.sort(key=lambda g: -g[1])
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return Reduced(
        window_s=window_s, busy_s=busy_total / len(chips),
        programs=dict(programs), int8_kernel=kernel,
        breakdown={"device_ops": [[n, t] for n, t in top_ops],
                   "idle_gaps": [[n, t] for n, t in gaps[:TOP]]})


def reduce_dir(directory: Path, window_s: float) -> Reduced:
    """Reduce the newest trace the profiler wrote under ``directory``."""
    from jax.profiler import ProfileData
    files = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return reduce_profile(ProfileData.from_file(str(files[-1])), window_s)

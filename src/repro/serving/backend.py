"""Pluggable inference backends: phase execution + costing behind one
protocol, so the serving event loops never care where numbers come from.

The engines (:class:`~repro.serving.engine.ServeEngine`,
:class:`~repro.serving.cluster.ClusterEngine`) and the
:class:`~repro.core.profiler.PhaseProfiler` are *schedulers*: they
decide which phase runs next (queueing, slot assignment, KV paging,
idle gaps). A :class:`InferenceBackend` owns what one phase *costs* —
and, optionally, what it *computes*:

* :class:`AnalyticBackend` — the paper's phase-aware analytic energy
  model (:mod:`repro.core.energy` over :mod:`repro.core.workload`),
  bit-identical to the pre-backend engine's accounting;
* :class:`ExecutedBackend` — analytic costing plus genuine JAX model
  steps (greedy decoding) through the same scheduler, including the
  decode-cache slot management (``repro.batching.continuous``);
* :class:`ReplayBackend` — replays a recorded per-phase latency/power
  trace (JSON, schema below), so real hardware measurements — e.g.
  NVML-sampled H100 phases — drive the simulator's scheduler;
* :class:`RecordingBackend` — wraps any backend and records its phase
  stream into that same JSON format (the analytic -> replay round trip
  is how the format is validated end to end).

Every phase call returns a :class:`PhaseResult` (latency, energy,
tokens, batch); DVFS-aware backends consult the engine's
:class:`~repro.core.hardware.DeviceSpec` operating point
(``DeviceSpec.with_freq_scale``), which scales compute throughput
linearly and dynamic power non-linearly while leaving the HBM clock
domain alone.

Recorded-trace schema (``repro-replay/v1``)::

    {
      "schema": "repro-replay/v1",
      "device": "h100-sxm",            # provenance, informational
      "model": "llama-3.1-8b",
      "source": "nvml sweep 2026-07",
      "idle_power_w": 120.0,
      "gated_power_w": 45.0,
      "prefill": [{"batch": 4, "pad_len": 1024,
                   "latency_s": 0.021, "power_w": 612.0}, ...],
      "decode":  [{"batch": 16, "cache_len": 1000,
                   "latency_s": 0.0093, "power_w": 371.0}, ...]
    }

Lookup is nearest-recorded-sample in log space over (batch, length);
prefill latency scales linearly with total padded tokens relative to
the chosen sample, decode steps replay the sample latency as-is.

Run ``python -m repro.serving.backend --selfcheck`` for the protocol
conformance check CI gates on.
"""
from __future__ import annotations

import abc
import dataclasses
import json
import math
import time
from typing import (TYPE_CHECKING, Any, Dict, List, Mapping, Optional,
                    Tuple)

import numpy as np

from repro.configs.base import ModelConfig
from repro.core import workload as W
from repro.core.energy import EnergyModel, EnergyReport
from repro.core.hardware import DeviceSpec, H100_SXM, check_executed_device
from repro.core.precision import PrecisionPolicy, make_policy
from repro.batching.policy import SlotCountPolicy
from repro.serving import spans

if TYPE_CHECKING:   # event-horizon boundaries (duck-typed at runtime)
    from repro.serving.scheduler import HorizonStop

REPLAY_SCHEMA = "repro-replay/v1"
BACKENDS = ("analytic", "executed", "replay")


# ---------------------------------------------------------------------------
# protocol data types
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PhaseResult:
    """What one executed phase cost (and produced)."""

    phase: str                  # "prefill" | "decode" | "idle" | "gated"
    latency_s: float
    energy_j: float
    tokens: int = 0             # new tokens this phase produced
    batch: float = 0.0          # live batch during the phase
    bound: Optional[str] = None  # analytic regime, when the backend knows

    @property
    def power_w(self) -> float:
        return self.energy_j / max(self.latency_s, 1e-12)


@dataclasses.dataclass
class PrefillBatch:
    """One prefill iteration as the scheduler formed it.

    ``picks`` are ``(slot, request)`` pairs; slot is ``None`` in
    sequential mode (no decode-slot machinery). ``pad_len`` is the
    padded/bucketed sequence length the batch computes.

    Chunked prefill (``chunk_len > 0``): the batch covers
    ``chunk_len`` prompt tokens of a single request, attending to the
    ``chunk_start`` tokens already in its KV cache.  Chunks are exact,
    so ``pad_len == chunk_len``; replay backends therefore price them
    through the ordinary padded-token scaling with no schema change."""

    picks: List[Tuple[Optional[int], Any]]
    pad_len: int
    stack: str = "fused"
    chunk_start: int = 0
    chunk_len: int = 0

    @property
    def n(self) -> int:
        return len(self.picks)

    @property
    def requests(self) -> List[Any]:
        return [r for _, r in self.picks]


@dataclasses.dataclass
class DecodeBatch:
    """One decode step over the live slots."""

    slots: List[int]
    requests: List[Any]
    cache_lens: List[int]       # per-request prompt + generated tokens
    stack: str = "fused"

    @property
    def n(self) -> int:
        return len(self.slots)


@dataclasses.dataclass
class DecodeRun:
    """Result of a fused run of decode steps over a frozen live batch
    (the engine's event-horizon macro-step).

    Per-step latencies/energies are kept so the engine can reproduce
    the single-step accumulation order exactly — ``t_end`` is
    ``t_start`` folded with the latencies in sequence, the same float
    additions the per-step loop would have performed.
    """

    latencies_s: np.ndarray     # (n_steps,)
    energies_j: np.ndarray      # (n_steps,)
    t_end: float
    tokens_per_step: int        # == batch size (one token per live slot)
    bound: Optional[str] = None
    # start time of the run's FINAL step (== t_start when the run is a
    # single step). The fleet loop needs it to decide whether a clipped
    # legacy run would already have executed that final step — i.e.
    # when completions collected by an over-advanced run become visible
    # to the serial cluster loop.
    t_penult: float = 0.0

    @property
    def n_steps(self) -> int:
        return len(self.latencies_s)

    @property
    def tokens(self) -> int:
        return self.n_steps * self.tokens_per_step


# ---------------------------------------------------------------------------
# protocol
# ---------------------------------------------------------------------------
class InferenceBackend(abc.ABC):
    """Phase execution + costing behind the serving event loops.

    Required: the three phase methods (``prefill`` / ``decode_step`` /
    ``idle``) plus ``decode_tail`` (sequential-mode bulk decode).
    Optional hooks: ``start`` (per-run reset), ``release_slot``
    (decode-slot evict), ``finish_request`` (sequential-mode
    post-request work, e.g. real generation).
    """

    name: str = "base"

    def start(self) -> None:
        """Per-run reset (fresh decode cache, replay cursor, ...)."""

    @abc.abstractmethod
    def prefill(self, batch: PrefillBatch) -> PhaseResult:
        """Execute one (possibly batched, padded) prefill."""

    @abc.abstractmethod
    def decode_step(self, batch: DecodeBatch) -> PhaseResult:
        """Execute ONE decode step for all live slots."""

    def decode_run(self, batch: DecodeBatch, max_steps: int, *,
                   t_start: float = 0.0,
                   stop: Optional["HorizonStop"] = None) -> DecodeRun:
        """Execute up to ``max_steps`` decode steps for a frozen live
        batch — the engine's event-horizon macro-step.

        ``batch.cache_lens`` describes the FIRST step; each later step
        sees every cache one token longer. When ``stop`` is given, the
        run ends after the first step whose end time (``t_start``
        folded with the per-step latencies) hits the boundary.

        The default implementation loops :meth:`decode_step` once per
        step, so backends that only implement single steps — including
        ones with real per-step side effects — keep working unchanged;
        cost-only backends may override with a fused path (see
        :meth:`AnalyticBackend.decode_run`). Either way results are
        bit-identical to the single-step loop.
        """
        if max_steps < 1:
            raise ValueError("decode_run needs max_steps >= 1")
        lats: List[float] = []
        ens: List[float] = []
        now = t_start
        penult = t_start
        bound = None
        cur = batch
        for j in range(max_steps):
            if j:
                cur = dataclasses.replace(
                    batch, cache_lens=[c + j for c in batch.cache_lens])
            res = self.decode_step(cur)
            lats.append(res.latency_s)
            ens.append(res.energy_j)
            if bound is None:
                bound = res.bound
            penult = now
            now += res.latency_s
            if stop is not None and stop.hit(now):
                break
        return DecodeRun(latencies_s=np.asarray(lats, dtype=np.float64),
                         energies_j=np.asarray(ens, dtype=np.float64),
                         t_end=float(now), tokens_per_step=batch.n,
                         bound=bound, t_penult=penult)

    @abc.abstractmethod
    def decode_tail(self, request: Any, n_steps: int,
                    stack: str = "eager") -> PhaseResult:
        """Bulk-cost ``n_steps`` sequential decode steps for one
        request (sequential mode folds the whole tail into one call)."""

    @abc.abstractmethod
    def idle(self, dt: float, state: str = "idle") -> PhaseResult:
        """Account ``dt`` seconds in a non-serving power state
        (``idle`` or ``gated``)."""

    def release_slot(self, slot: int) -> None:
        """A decode slot was freed (request finished) — evict any
        device-side state the backend keeps for it."""

    def finish_request(self, request: Any) -> None:
        """Sequential-mode hook after a request's phases were costed."""


_ARANGE = np.arange(1024, dtype=np.float64)
_ARANGE.flags.writeable = False


def _arange_f64(k: int) -> np.ndarray:
    """Read-only ``0..k-1`` float64 view (grown on demand) — saves an
    allocation per decode macro-step. The backing buffer is marked
    non-writeable so an accidental in-place op raises instead of
    corrupting every later macro-step."""
    global _ARANGE
    if k > len(_ARANGE):
        _ARANGE = np.arange(max(k, 2 * len(_ARANGE)), dtype=np.float64)
        _ARANGE.flags.writeable = False
    return _ARANGE[:k]


# ---------------------------------------------------------------------------
# analytic
# ---------------------------------------------------------------------------
class AnalyticBackend(InferenceBackend):
    """The paper's phase-aware analytic model as a backend.

    Costing is exactly the pre-backend engine's: workloads from
    :mod:`repro.core.workload` evaluated by an
    :class:`~repro.core.energy.EnergyModel` for this (device, policy,
    n_chips) — the parity tests pin bit-identical reports.
    """

    name = "analytic"

    def __init__(self, cfg: ModelConfig, *,
                 device: DeviceSpec = H100_SXM,
                 policy: Optional[PrecisionPolicy] = None,
                 fmt: str = "bfloat16", n_chips: int = 1,
                 energy_model_cls=EnergyModel,
                 energy_model: Optional[EnergyModel] = None):
        self.cfg = cfg
        self.device = device
        self.policy = policy if policy is not None else make_policy(fmt)
        self.n_chips = n_chips
        self.energy = (energy_model if energy_model is not None
                       else energy_model_cls(device, self.policy))
        # nominal-clock anchor for the DVFS actuator: re-targeting
        # derives from here, so repeated mid-run changes cannot drift
        self._nominal_device = device if device.freq_scale == 1.0 else None

    def set_freq_scale(self, target: float) -> None:
        """DVFS actuator (:mod:`repro.control`): move every subsequent
        phase to the operating point at ``target`` of the *nominal*
        clock. The device spec and energy model are rebuilt from the
        nominal anchor — not composed onto the current point — so a
        controller can re-target arbitrarily often without float
        drift in the operating point itself."""
        if target == self.device.freq_scale:
            return
        base = self._nominal_device
        if base is None:
            # constructed at a scaled point: recover the nominal spec
            # once (exact in freq/flops; power unwinds to ~1 ulp)
            unwound = self.device.with_freq_scale(
                1.0 / self.device.freq_scale)
            base = dataclasses.replace(
                unwound, name=self.device.name.split("@f")[0],
                freq_scale=1.0)
            self._nominal_device = base
        self.device = base.with_freq_scale(target)
        self.energy = type(self.energy)(self.device, self.policy)

    # -- EnergyReport-level entry points (PhaseProfiler consumes these) -
    def prefill_report(self, batch: int, seq: int,
                       stack: str = "eager") -> EnergyReport:
        return self.energy.evaluate(
            W.prefill_workload(self.cfg, batch, seq, stack=stack),
            self.n_chips)

    def decode_step_report(self, batch: int, cache_len: int,
                           stack: str = "eager") -> EnergyReport:
        return self.energy.evaluate(
            W.decode_step_workload(self.cfg, batch, cache_len,
                                   stack=stack), self.n_chips)

    def decode_report(self, batch: int, prompt_len: int, new_tokens: int,
                      stack: str = "eager") -> EnergyReport:
        return self.energy.evaluate(
            W.decode_workload(self.cfg, batch, prompt_len, new_tokens,
                              stack=stack), self.n_chips)

    def train_report(self, batch: int, seq: int,
                     stack: str = "fused") -> EnergyReport:
        return self.energy.evaluate(
            W.train_step_workload(self.cfg, batch, seq, stack=stack),
            self.n_chips)

    # -- protocol -------------------------------------------------------
    def prefill(self, batch: PrefillBatch) -> PhaseResult:
        if batch.chunk_len:
            # partial prefill: chunk_len new prompt tokens attending to
            # the chunk_start tokens already cached (weights re-read per
            # chunk — the real cost of chunking)
            rep = self.energy.evaluate(
                W.prefill_chunk_workload(self.cfg, batch.n,
                                         batch.chunk_len,
                                         batch.chunk_start,
                                         stack=batch.stack),
                self.n_chips)
        else:
            rep = self.prefill_report(batch.n, batch.pad_len,
                                      stack=batch.stack)
        return PhaseResult(phase="prefill", latency_s=rep.latency,
                           energy_j=rep.energy_j, tokens=batch.n,
                           batch=float(batch.n), bound=rep.bound)

    def decode_step(self, batch: DecodeBatch) -> PhaseResult:
        rep = self.decode_step_report(
            batch.n, int(np.mean(batch.cache_lens)), stack=batch.stack)
        return PhaseResult(phase="decode", latency_s=rep.latency,
                           energy_j=rep.energy_j, tokens=batch.n,
                           batch=float(batch.n), bound=rep.bound)

    def decode_run(self, batch: DecodeBatch, max_steps: int, *,
                   t_start: float = 0.0,
                   stop: Optional["HorizonStop"] = None) -> DecodeRun:
        """Fused macro-step: cost all ``max_steps`` in one vectorized
        energy-model evaluation instead of ``max_steps`` Python
        iterations. Bit-identical to the :meth:`decode_step` loop —
        per-step mean cache lengths, workload terms, and the
        ``t_start`` latency fold replicate the scalar arithmetic
        exactly (pinned by the macro-stepping parity tests)."""
        if max_steps < 1:
            raise ValueError("decode_run needs max_steps >= 1")
        n = batch.n
        # per-step int(np.mean(cache_lens)): every cache grows by one
        # token per step, so the (exact-integer) sum grows by n; the
        # float division below is the same division np.mean performs
        s0 = sum(batch.cache_lens)
        sums = (np.float64(s0)
                + np.float64(n) * _arange_f64(max_steps))
        ctx = (sums / np.float64(n)).astype(np.int64)
        template, flops, act = W.decode_step_arrays(
            self.cfg, n, ctx, stack=batch.stack)
        lat, en, bound = self.energy.evaluate_steps(
            template, flops, act, self.n_chips)
        buf = np.empty(max_steps + 1)
        buf[0] = t_start
        buf[1:] = lat
        nows = np.add.accumulate(buf)[1:]   # strict left fold
        j = max_steps if stop is None else stop.n_steps(nows)
        return DecodeRun(latencies_s=lat[:j], energies_j=en[:j],
                         t_end=float(nows[j - 1]), tokens_per_step=n,
                         bound=bound,
                         t_penult=(float(nows[j - 2]) if j > 1
                                   else t_start))

    def decode_tail(self, request: Any, n_steps: int,
                    stack: str = "eager") -> PhaseResult:
        rep = self.decode_report(1, request.prompt_len, n_steps,
                                 stack=stack)
        return PhaseResult(phase="decode", latency_s=rep.latency,
                           energy_j=rep.energy_j, tokens=n_steps,
                           batch=1.0, bound=rep.bound)

    def idle(self, dt: float, state: str = "idle") -> PhaseResult:
        return PhaseResult(phase=state, latency_s=dt,
                           energy_j=self.device.state_power(state) * dt)


# ---------------------------------------------------------------------------
# executed
# ---------------------------------------------------------------------------
def jit_decode_step(model):
    """The served decode step, jitted with the cache (argument 2)
    donated: the step writes its tokens into the cache in place, and the
    caller rebinds its cache to the one returned."""
    import jax
    return jax.jit(model.decode_step, donate_argnums=(2,))


class ExecutedBackend(AnalyticBackend):
    """Analytic costing + genuine JAX execution through the scheduler.

    The simulation clock stays analytic (the quantity the paper
    measures per phase); real prefill/decode steps run greedily through
    the same slot assignments, pinning scheduler semantics to real
    computation. Decode-cache slot insert/evict lives in
    :mod:`repro.batching.continuous` (single owner).
    """

    name = "executed"

    def __init__(self, cfg: ModelConfig, model, params, *,
                 max_batch: int, buf_len: int = 256, **analytic_kw):
        super().__init__(cfg, **analytic_kw)
        assert model is not None and params is not None
        import jax
        dev = jax.devices()[0]
        check_executed_device(self.device, dev.platform, dev.device_kind)
        if (dev.platform == "tpu" and model.policy.is_quantized
                and not model.policy.use_pallas_kernels):
            raise ValueError(
                f"fmt={model.policy.fmt!r} on a TPU must run the compiled "
                "quant_matmul kernel; build the model with "
                "use_pallas_kernels=True")
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.buf_len = buf_len
        self._jit_decode = jit_decode_step(model)
        self._jit_prefill = jax.jit(
            lambda p, b, l: model.prefill(p, b, buf_len=buf_len,
                                          lengths=l))
        self.start()

    def start(self) -> None:
        import jax.numpy as jnp
        self.cache = self.model.init_cache(self.max_batch, self.buf_len)
        self.slot_tokens = jnp.zeros((self.max_batch, 1), jnp.int32)

    # -- protocol -------------------------------------------------------
    def prefill(self, batch: PrefillBatch) -> PhaseResult:
        with spans.span(spans.COST, phase="prefill"):
            res = super().prefill(batch)
        if any(slot is not None for slot, _ in batch.picks):
            if batch.chunk_len:
                # chunk costing is analytic (above); the genuine model
                # prefill runs once, on the final chunk, over the full
                # prompt — same computed tokens, same greedy outputs
                _, r = batch.picks[0]
                if batch.chunk_start + batch.chunk_len >= r.prompt_len:
                    self._execute_prefill(batch.picks)
            else:
                self._execute_prefill(batch.picks)
        return res

    def decode_step(self, batch: DecodeBatch) -> PhaseResult:
        with spans.span(spans.COST, phase="decode"):
            res = super().decode_step(batch)
        self._execute_decode(batch)
        return res

    def decode_run(self, batch: DecodeBatch, max_steps: int, *,
                   t_start: float = 0.0,
                   stop: Optional["HorizonStop"] = None) -> DecodeRun:
        # real execution is inherently stepwise: use the protocol's
        # decode_step fallback (each step runs the model; the analytic
        # clock it returns is identical to the fused path's)
        return InferenceBackend.decode_run(self, batch, max_steps,
                                           t_start=t_start, stop=stop)

    def release_slot(self, slot: int) -> None:
        # zeroing just the feed token keeps freed lanes deterministic;
        # the full cache-lane evict (continuous.evict_cache_slot) is
        # deliberately NOT run per finish — lanes are independent, so
        # stale state cannot change live outputs, and the copy would
        # cost a full cache allocation per completed request
        self.slot_tokens = self.slot_tokens.at[slot, 0].set(0)

    def finish_request(self, request: Any) -> None:
        """Sequential mode: run the real greedy generation end to end
        (fresh per-request cache, no slot machinery)."""
        import jax.numpy as jnp
        r = request
        toks = jnp.asarray(r.prompt[None, :], jnp.int32)
        logits, cache = self.model.prefill(
            self.params, {"tokens": toks},
            buf_len=r.prompt_len + r.max_new_tokens + 1)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        r.generated = [int(tok[0, 0])]
        for _ in range(r.max_new_tokens - 1):
            logits, cache = self.model.decode_step(self.params, tok, cache)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            r.generated.append(int(tok[0, 0]))

    # -- real execution -------------------------------------------------
    def _execute_prefill(self, picks) -> None:
        """Run the real prefill. Note: execution pads to the batch max
        (multiple of 8), not to the energy-model's bucket — the bucket
        models *computed* tokens for accounting and may exceed the
        engine's KV buffer."""
        import jax.numpy as jnp
        from repro.batching.continuous import insert_cache_slot
        exec_pad = max(r.prompt_len for _, r in picks)
        exec_pad = min(((exec_pad + 7) // 8) * 8, self.buf_len)
        toks = np.zeros((len(picks), exec_pad), np.int32)
        lens = np.zeros((len(picks),), np.int32)
        for j, (_, r) in enumerate(picks):
            toks[j, :r.prompt_len] = r.prompt[:exec_pad]
            lens[j] = r.prompt_len
        t_launch = time.perf_counter()
        for _, r in picks:
            r.t_launch_host = t_launch
        with spans.span(spans.LAUNCH, program="prefill", rows=len(picks)):
            logits, pcache = self._jit_prefill(
                self.params, {"tokens": jnp.asarray(toks)},
                jnp.asarray(lens))
            ids = jnp.argmax(logits, -1)
        with spans.span(spans.SYNC, program="prefill"):
            first = np.asarray(ids)
        for j, (slot, r) in enumerate(picks):
            r.generated = [int(first[j])]
            with spans.span(spans.INSERT, slot=slot, req=r.req_id):
                self.cache = insert_cache_slot(self.cache, pcache, j, slot)
                self.slot_tokens = self.slot_tokens.at[slot, 0].set(
                    int(first[j]))

    def _execute_decode(self, batch: DecodeBatch) -> None:
        import jax.numpy as jnp
        with spans.span(spans.LAUNCH, program="decode",
                        rows=self.max_batch):
            logits, self.cache = self._jit_decode(
                self.params, self.slot_tokens, self.cache)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            self.slot_tokens = nxt[:, None]
        with spans.span(spans.SYNC, program="decode"):
            arr = np.asarray(nxt)
        for slot, req in zip(batch.slots, batch.requests):
            req.generated.append(int(arr[slot]))


# ---------------------------------------------------------------------------
# replay
# ---------------------------------------------------------------------------
def _nearest(samples: List[Mapping[str, float]], keys: Tuple[str, str],
             batch: float, length: float) -> Mapping[str, float]:
    """Nearest recorded sample in log space over (batch, length) —
    deterministic: ties resolve to the earliest sample in file order."""
    def dist(s) -> float:
        return (math.log(max(batch, 1) / max(s[keys[0]], 1)) ** 2
                + math.log(max(length, 1) / max(s[keys[1]], 1)) ** 2)
    return min(samples, key=dist)


class ReplayBackend(InferenceBackend):
    """Replay a recorded per-phase latency/power trace.

    The scheduler stays fully live (queueing, batching, KV paging);
    only the *cost source* is swapped for measurements — so a set of
    real H100 phase samples can drive every serving experiment the
    simulator supports (arrival shaping, routing, admission control).
    """

    name = "replay"

    def __init__(self, trace: Mapping[str, Any]):
        if trace.get("schema") != REPLAY_SCHEMA:
            raise ValueError(
                f"unsupported replay schema {trace.get('schema')!r}; "
                f"expected {REPLAY_SCHEMA!r}")
        for phase in ("prefill", "decode"):
            if not trace.get(phase):
                raise ValueError(f"replay trace has no {phase!r} samples")
        if "idle_power_w" not in trace:
            raise ValueError(
                "replay trace missing 'idle_power_w' — idle/gated gaps "
                "would silently be billed at 0 W")
        self.trace = trace
        self.prefill_samples = [dict(s) for s in trace["prefill"]]
        self.decode_samples = [dict(s) for s in trace["decode"]]
        self.idle_power_w = float(trace.get("idle_power_w", 0.0))
        self.gated_power_w = float(
            trace.get("gated_power_w", self.idle_power_w))
        for s in self.prefill_samples:
            self._check_sample(s, "pad_len")
        for s in self.decode_samples:
            self._check_sample(s, "cache_len")
        # DVFS actuation state: pristine recorded samples + the current
        # operating point relative to the recorded clock
        self._prefill_recorded = [dict(s) for s in self.prefill_samples]
        self._decode_recorded = [dict(s) for s in self.decode_samples]
        self.freq_scale = 1.0

    def set_freq_scale(self, target: float) -> None:
        """DVFS actuator for replayed traces: extrapolate the recorded
        samples to the operating point at ``target`` of the recorded
        clock. Measurements only exist at the recorded point, so this
        is an explicit model-based extrapolation using the same
        dynamic-power law as :meth:`DeviceSpec.with_freq_scale` —
        prefill is treated as compute-bound (latency scales ``1/f``,
        power above the idle floor scales ``f^3``), decode as
        memory-bound (latency unchanged, dynamic power ``f^3``), and
        the idle/gated floors are unchanged. It exists so closed-loop
        controllers can be evaluated against recorded hardware traces;
        static replay sweeps should instead record the trace at the
        target operating point."""
        if target <= 0:
            raise ValueError(f"freq_scale must be positive, got {target}")
        if not 0.1 <= target <= 1.5:
            raise ValueError(f"freq_scale {target:g} outside [0.1, 1.5]")
        if target == self.freq_scale:
            return
        self.freq_scale = float(target)
        u = float(target)
        floor = self.idle_power_w

        def dyn(p: float) -> float:
            return floor + max(p - floor, 0.0) * u ** 3

        self.prefill_samples = [
            dict(s, latency_s=s["latency_s"] / u,
                 power_w=dyn(s["power_w"]))
            for s in self._prefill_recorded]
        self.decode_samples = [
            dict(s, power_w=dyn(s["power_w"]))
            for s in self._decode_recorded]

    @staticmethod
    def _check_sample(s: Mapping[str, float], length_key: str) -> None:
        for field in ("batch", length_key, "latency_s", "power_w"):
            if field not in s:
                raise ValueError(f"replay sample missing {field!r}: {s}")
            if not s[field] >= 0:
                raise ValueError(f"replay sample field {field!r} must "
                                 f"be >= 0: {s}")

    @classmethod
    def from_json(cls, path: str) -> "ReplayBackend":
        with open(path) as f:
            return cls(json.load(f))

    # -- protocol -------------------------------------------------------
    def prefill(self, batch: PrefillBatch) -> PhaseResult:
        s = _nearest(self.prefill_samples, ("batch", "pad_len"),
                     batch.n, batch.pad_len)
        # prefill cost is ~linear in computed tokens: scale the sample's
        # latency by the padded-token ratio, keep its measured power
        tokens = batch.n * batch.pad_len
        ref = max(s["batch"] * s["pad_len"], 1.0)
        latency = s["latency_s"] * tokens / ref
        return PhaseResult(phase="prefill", latency_s=latency,
                           energy_j=s["power_w"] * latency,
                           tokens=batch.n, batch=float(batch.n),
                           bound="replay")

    def decode_step(self, batch: DecodeBatch) -> PhaseResult:
        s = _nearest(self.decode_samples, ("batch", "cache_len"),
                     batch.n, float(np.mean(batch.cache_lens)))
        return PhaseResult(phase="decode", latency_s=s["latency_s"],
                           energy_j=s["power_w"] * s["latency_s"],
                           tokens=batch.n, batch=float(batch.n),
                           bound="replay")

    def decode_tail(self, request: Any, n_steps: int,
                    stack: str = "eager") -> PhaseResult:
        s = _nearest(self.decode_samples, ("batch", "cache_len"),
                     1, request.prompt_len + n_steps / 2)
        latency = s["latency_s"] * n_steps
        return PhaseResult(phase="decode", latency_s=latency,
                           energy_j=s["power_w"] * latency,
                           tokens=n_steps, batch=1.0, bound="replay")

    def idle(self, dt: float, state: str = "idle") -> PhaseResult:
        p = self.gated_power_w if state == "gated" else self.idle_power_w
        return PhaseResult(phase=state, latency_s=dt, energy_j=p * dt)


# ---------------------------------------------------------------------------
# recording
# ---------------------------------------------------------------------------
class RecordingBackend(InferenceBackend):
    """Record another backend's phase stream into the replay format.

    Samples are aggregated per (batch, length) operating point (mean
    latency/power; decode cache lengths bucketed to
    ``cache_len_bucket``), so a long run collapses into a compact
    trace — the same shape a real NVML phase sweep produces.
    """

    name = "recording"

    def __init__(self, inner: InferenceBackend, *,
                 cache_len_bucket: int = 64):
        self.inner = inner
        self.cache_len_bucket = max(int(cache_len_bucket), 1)
        # forward the inner cost model's identity so engines (and their
        # routers/schedulers) price with what is actually being billed
        for attr in ("device", "energy", "cfg", "policy"):
            if hasattr(inner, attr):
                setattr(self, attr, getattr(inner, attr))
        self._prefill: Dict[Tuple[int, int], List[PhaseResult]] = {}
        self._decode: Dict[Tuple[int, int], List[PhaseResult]] = {}
        self._idle_power: Dict[str, float] = {}

    def start(self) -> None:
        self.inner.start()

    def prefill(self, batch: PrefillBatch) -> PhaseResult:
        res = self.inner.prefill(batch)
        self._prefill.setdefault((batch.n, batch.pad_len),
                                 []).append(res)
        return res

    def _decode_key(self, batch: int, cache_len: float) -> Tuple[int, int]:
        b = self.cache_len_bucket
        return (batch, max(int(round(cache_len / b)) * b, 1))

    def decode_step(self, batch: DecodeBatch) -> PhaseResult:
        res = self.inner.decode_step(batch)
        key = self._decode_key(batch.n, float(np.mean(batch.cache_lens)))
        self._decode.setdefault(key, []).append(res)
        return res

    def decode_tail(self, request: Any, n_steps: int,
                    stack: str = "eager") -> PhaseResult:
        res = self.inner.decode_tail(request, n_steps, stack=stack)
        key = self._decode_key(1, request.prompt_len + n_steps / 2)
        # one tail = n_steps steps at the mid-cache point
        self._decode.setdefault(key, []).append(
            PhaseResult(phase="decode",
                        latency_s=res.latency_s / max(n_steps, 1),
                        energy_j=res.energy_j / max(n_steps, 1),
                        tokens=1, batch=1.0))
        return res

    def idle(self, dt: float, state: str = "idle") -> PhaseResult:
        res = self.inner.idle(dt, state)
        self._idle_power[state] = res.power_w
        return res

    def release_slot(self, slot: int) -> None:
        self.inner.release_slot(slot)

    def finish_request(self, request: Any) -> None:
        self.inner.finish_request(request)

    # -- export ---------------------------------------------------------
    def _state_power(self, state: str) -> float:
        """Recorded gap wattage; a run with no idle/gated gaps falls
        back to the inner backend's device so the trace never exports a
        silent 0 W idle state."""
        if state in self._idle_power:
            return self._idle_power[state]
        if state == "gated" and "idle" in self._idle_power:
            return self._idle_power["idle"]
        dev = getattr(self.inner, "device", None)
        if dev is not None:
            try:
                return dev.state_power(state)
            except ValueError:
                pass
        return 0.0

    def to_trace(self, device: str = "", model: str = "",
                 source: str = "recorded by RecordingBackend") -> Dict:
        def agg(table, length_key):
            return [{"batch": b, length_key: ln,
                     "latency_s": float(np.mean(
                         [r.latency_s for r in rs])),
                     "power_w": float(np.mean([r.power_w for r in rs]))}
                    for (b, ln), rs in sorted(table.items())]
        return {
            "schema": REPLAY_SCHEMA,
            "device": device, "model": model, "source": source,
            "idle_power_w": self._state_power("idle"),
            "gated_power_w": self._state_power("gated"),
            "prefill": agg(self._prefill, "pad_len"),
            "decode": agg(self._decode, "cache_len"),
        }

    def dump(self, path: str, **meta) -> Dict:
        trace = self.to_trace(**meta)
        with open(path, "w") as f:
            json.dump(trace, f, indent=1, sort_keys=True)
        return trace


# ---------------------------------------------------------------------------
# factory
# ---------------------------------------------------------------------------
def make_backend(name: str, cfg: ModelConfig, **kw) -> InferenceBackend:
    """Resolve a backend axis value. ``executed`` needs ``model`` /
    ``params`` / ``max_batch``; ``replay`` needs ``replay_path``."""
    if name == "analytic":
        return AnalyticBackend(cfg, **kw)
    if name == "executed":
        return ExecutedBackend(cfg, kw.pop("model"), kw.pop("params"),
                               **kw)
    if name == "replay":
        return ReplayBackend.from_json(kw.pop("replay_path"))
    raise ValueError(f"unknown backend {name!r}; known: {BACKENDS}")


# ---------------------------------------------------------------------------
# selfcheck (CI: python -m repro.serving.backend --selfcheck)
# ---------------------------------------------------------------------------
def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def _finite_result(res: PhaseResult, phase: str) -> None:
    _check(isinstance(res, PhaseResult),
           f"{phase}: backend must return PhaseResult, got {type(res)}")
    _check(res.phase in ("prefill", "decode", "idle", "gated"),
           f"{phase}: bad phase tag {res.phase!r}")
    for field in ("latency_s", "energy_j"):
        v = getattr(res, field)
        _check(np.isfinite(v) and v >= 0.0,
               f"{phase}: non-finite/negative {field}={v}")


def _conformance(backend: InferenceBackend, reqs) -> None:
    """Drive the raw protocol surface once and validate every result."""
    backend.start()
    r = reqs[0]
    _finite_result(backend.prefill(
        PrefillBatch(picks=[(None, r)], pad_len=r.prompt_len,
                     stack="eager")), "prefill")
    _finite_result(backend.decode_step(
        DecodeBatch(slots=[0], requests=[r],
                    cache_lens=[r.prompt_len + 1])), "decode_step")
    run = backend.decode_run(
        DecodeBatch(slots=[0], requests=[r],
                    cache_lens=[r.prompt_len + 2]), 4, t_start=1.0)
    _check(isinstance(run, DecodeRun) and run.n_steps == 4,
           f"decode_run must return a 4-step DecodeRun, got {run}")
    _check(np.isfinite(run.t_end) and run.t_end >= 1.0,
           f"decode_run t_end must fold from t_start, got {run.t_end}")
    _finite_result(backend.decode_tail(r, 4), "decode_tail")
    for state in ("idle", "gated"):
        res = backend.idle(0.5, state)
        _finite_result(res, f"idle[{state}]")
        _check(res.phase == state, f"idle must tag state {state!r}")
    backend.release_slot(0)


def selfcheck(verbose: bool = True) -> int:
    """Protocol-conformance + parity smoke over all shipped backends."""
    from repro.configs.paper_zoo import PAPER_MODELS
    from repro.serving.engine import ServeEngine
    from repro.serving.requests import Request

    def log(msg: str) -> None:
        if verbose:
            print(f"[backend-selfcheck] {msg}")

    cfg = PAPER_MODELS["llama-3.1-8b"]
    reqs = lambda: [Request(req_id=i, prompt=None, prompt_len=256,  # noqa: E731
                            max_new_tokens=8, arrival_time=0.05 * i)
                    for i in range(8)]

    # 1. analytic: conformance + default-engine parity
    analytic = AnalyticBackend(cfg)
    _conformance(analytic, reqs())
    rep_default = ServeEngine(cfg, batch_policy=SlotCountPolicy(max_batch=4)).run(reqs())
    rep_explicit = ServeEngine(cfg,
                               backend=AnalyticBackend(cfg), batch_policy=SlotCountPolicy(max_batch=4)).run(reqs())
    _check(rep_default.total_energy_j == rep_explicit.total_energy_j
           and rep_default.wall_time_s == rep_explicit.wall_time_s,
           "explicit AnalyticBackend diverges from the default engine")
    log(f"analytic ok ({rep_default.total_energy_j:.1f} J)")

    # 1b. macro-step fusion: the vectorized decode_run must equal the
    # protocol's stepwise fallback bit for bit
    rs = reqs()[:2]
    batch = DecodeBatch(slots=[0, 1], requests=rs,
                        cache_lens=[r.prompt_len + 1 for r in rs])
    fused = analytic.decode_run(batch, 16, t_start=0.25)
    stepped = InferenceBackend.decode_run(analytic, batch, 16,
                                          t_start=0.25)
    _check(bool((fused.latencies_s == stepped.latencies_s).all()
                and (fused.energies_j == stepped.energies_j).all()
                and fused.t_end == stepped.t_end),
           "vectorized decode_run diverges from the stepwise fallback")
    log(f"decode_run ok (16 fused steps, t_end {fused.t_end:.4f}s)")

    # 2. replay: record the analytic run, replay it, compare
    rec = RecordingBackend(AnalyticBackend(cfg))
    ServeEngine(cfg, backend=rec, batch_policy=SlotCountPolicy(max_batch=4)).run(reqs())
    replay = ReplayBackend(rec.to_trace(device="h100-sxm",
                                        model=cfg.name))
    _conformance(replay, reqs())
    rep_replay = ServeEngine(cfg, backend=replay, batch_policy=SlotCountPolicy(max_batch=4)).run(reqs())
    drift = (rep_replay.total_energy_j
             / max(rep_default.total_energy_j, 1e-12))
    _check(0.9 < drift < 1.1,
           f"replay round trip drifted {drift:.3f}x from analytic")
    log(f"replay ok (round-trip drift {drift:.4f}x)")

    # 3. executed: real JAX steps through the scheduler (reduced model)
    from repro.configs import get_config
    from repro.models import build_model
    import jax
    rcfg = get_config("stablelm-1.6b").reduced()
    model = build_model(rcfg, fmt="float32")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    ereqs = [Request(req_id=i,
                     prompt=rng.integers(0, rcfg.vocab_size, 8)
                     .astype(np.int32),
                     prompt_len=8, max_new_tokens=3, arrival_time=0.0)
             for i in range(3)]
    backend = ExecutedBackend(rcfg, model, params, max_batch=4,
                              buf_len=32, fmt="float32")
    rep = ServeEngine(rcfg, fmt="float32", buf_len=32,
                      backend=backend, batch_policy=SlotCountPolicy(max_batch=4)).run(ereqs)
    _check(all(len(r.generated) == r.max_new_tokens
               for r in rep.requests),
           "executed backend did not generate real tokens")
    log("executed ok (real tokens generated through the scheduler)")

    # 4. DVFS: scaled device spec keeps the protocol honest
    dev = H100_SXM.with_freq_scale(0.7)
    _check(dev.peak_flops_16 < H100_SXM.peak_flops_16
           and dev.power_memory < H100_SXM.power_memory
           and dev.hbm_bw == H100_SXM.hbm_bw,
           "with_freq_scale must scale compute/power but not HBM")
    scaled = AnalyticBackend(cfg, device=dev)
    _conformance(scaled, reqs())
    log(f"dvfs ok ({dev.name}: {dev.power_memory:.0f} W memory-bound)")

    log("all backends conform")
    return 0


def _main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="InferenceBackend protocol utilities")
    ap.add_argument("--selfcheck", action="store_true",
                    help="run the protocol-conformance check (CI gate)")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    if args.selfcheck:
        return selfcheck(verbose=not args.quiet)
    ap.print_help()
    return 2


if __name__ == "__main__":
    # `python -m` executes a second copy of this module body; re-enter
    # through the canonical import so the selfcheck's backend classes
    # share identity with the ones the engines isinstance-check
    from repro.serving import backend as _canonical
    raise SystemExit(_canonical._main())

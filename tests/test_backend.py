"""InferenceBackend protocol tests: analytic parity with the
pre-refactor engine (bit-identical golden reports), executed-backend
equivalence with the legacy ``execute=True`` path, replay round trips,
DVFS device scaling, and the ServeReport empty-run guards."""
import json
import os

import numpy as np
import pytest

from repro.api import ExperimentSpec
from repro.configs import get_config
from repro.configs.paper_zoo import PAPER_MODELS
from repro.core.hardware import H100_SXM, TPU_V5E
from repro.core.profiler import PhaseProfiler
from repro.serving.backend import (AnalyticBackend, DecodeBatch,
                                   ExecutedBackend, PhaseResult,
                                   PrefillBatch, RecordingBackend,
                                   ReplayBackend, REPLAY_SCHEMA,
                                   make_backend)
from repro.serving.engine import ServeEngine, ServeReport
from repro.serving.requests import Request
from repro.batching.policy import SlotCountPolicy

LLAMA8B = PAPER_MODELS["llama-3.1-8b"]
DATA = os.path.join(os.path.dirname(__file__), "data")
FIXTURE = os.path.join(DATA, "replay_h100_small.json")


def _reqs(n, *, plen=256, out=8, gap=0.05):
    return [Request(req_id=i, prompt=None, prompt_len=plen,
                    max_new_tokens=out, arrival_time=gap * i)
            for i in range(n)]


# ---------------------------------------------------------------------------
# analytic parity: the refactor must not move a single bit
# ---------------------------------------------------------------------------
class TestGoldenParity:
    """Every RunResult captured from the pre-backend engine must
    reproduce byte-identically (spec hash included)."""

    with open(os.path.join(DATA, "golden_pre_refactor.json")) as f:
        GOLDEN = json.load(f)["records"]

    @pytest.mark.parametrize("name", sorted(GOLDEN))
    def test_reproduces_pre_refactor_record(self, name):
        rec = self.GOLDEN[name]
        spec = ExperimentSpec.from_dict(rec["spec"])
        assert spec.spec_hash() == rec["spec_hash"], \
            "spec serialization drifted from the pre-refactor hash"
        assert spec.run().to_json() == rec["result"]

    def test_explicit_analytic_backend_is_default(self):
        a = ServeEngine(LLAMA8B, batch_policy=SlotCountPolicy(max_batch=8)).run(_reqs(20))
        b = ServeEngine(LLAMA8B,
                        backend=AnalyticBackend(LLAMA8B), batch_policy=SlotCountPolicy(max_batch=8)).run(_reqs(20))
        assert a.total_energy_j == b.total_energy_j
        assert a.wall_time_s == b.wall_time_s
        assert a.busy_energy_j == b.busy_energy_j
        assert [r.t_done for r in a.requests] == \
            [r.t_done for r in b.requests]

    def test_profiler_backend_parity(self):
        default = PhaseProfiler(LLAMA8B)
        explicit = PhaseProfiler(LLAMA8B,
                                 backend=AnalyticBackend(
                                     LLAMA8B, n_chips=1))
        assert (default.profile_prefill(4, 1200).energy_j
                == explicit.profile_prefill(4, 1200).energy_j)
        assert (default.profile_decode(4, 1200, 80).latency
                == explicit.profile_decode(4, 1200, 80).latency)


# ---------------------------------------------------------------------------
# protocol conformance
# ---------------------------------------------------------------------------
class TestProtocol:
    def _conform(self, backend):
        backend.start()
        r = _reqs(1)[0]
        pre = backend.prefill(PrefillBatch(picks=[(None, r)],
                                           pad_len=r.prompt_len,
                                           stack="eager"))
        dec = backend.decode_step(DecodeBatch(
            slots=[0], requests=[r], cache_lens=[r.prompt_len + 1]))
        tail = backend.decode_tail(r, 4)
        idle = backend.idle(1.0, "idle")
        gated = backend.idle(1.0, "gated")
        for res in (pre, dec, tail, idle, gated):
            assert isinstance(res, PhaseResult)
            assert np.isfinite(res.latency_s) and res.latency_s >= 0
            assert np.isfinite(res.energy_j) and res.energy_j >= 0
        assert pre.phase == "prefill" and dec.phase == "decode"
        assert idle.phase == "idle" and gated.phase == "gated"
        assert gated.energy_j <= idle.energy_j
        backend.release_slot(0)

    def test_analytic_conforms(self):
        self._conform(AnalyticBackend(LLAMA8B))

    def test_replay_conforms(self):
        self._conform(ReplayBackend.from_json(FIXTURE))

    def test_make_backend(self):
        assert isinstance(make_backend("analytic", LLAMA8B),
                          AnalyticBackend)
        assert isinstance(
            make_backend("replay", LLAMA8B, replay_path=FIXTURE),
            ReplayBackend)
        with pytest.raises(ValueError, match="unknown backend"):
            make_backend("nvml", LLAMA8B)


# ---------------------------------------------------------------------------
# replay
# ---------------------------------------------------------------------------
class TestReplay:
    def test_roundtrip_matches_analytic(self):
        """Record an analytic run, replay it through the same
        scheduler: the report reproduces within aggregation noise."""
        rec = RecordingBackend(AnalyticBackend(LLAMA8B))
        ref = ServeEngine(LLAMA8B, backend=rec, batch_policy=SlotCountPolicy(max_batch=8)).run(_reqs(24))
        replay = ReplayBackend(rec.to_trace(model=LLAMA8B.name))
        rep = ServeEngine(LLAMA8B,
                          backend=replay, batch_policy=SlotCountPolicy(max_batch=8)).run(_reqs(24))
        assert rep.total_energy_j == pytest.approx(
            ref.total_energy_j, rel=0.02)
        assert rep.wall_time_s == pytest.approx(ref.wall_time_s, rel=0.02)
        assert rep.n_decode_steps == ref.n_decode_steps

    def test_deterministic(self):
        backend = ReplayBackend.from_json(FIXTURE)
        a = ServeEngine(LLAMA8B, backend=backend, batch_policy=SlotCountPolicy(max_batch=8)).run(_reqs(16))
        b = ServeEngine(LLAMA8B, backend=backend, batch_policy=SlotCountPolicy(max_batch=8)).run(_reqs(16))
        assert a.total_energy_j == b.total_energy_j
        assert a.wall_time_s == b.wall_time_s

    def test_fixture_via_spec_axis(self):
        spec = ExperimentSpec(model="llama-3.1-8b", backend="replay",
                              replay_path=FIXTURE, n_requests=12,
                              max_batch=8)
        res = spec.run()
        assert res.n_requests == 12
        assert res.total_energy_j > 0
        # the replay axis is part of the spec's identity
        assert spec.spec_hash() != spec.derive(backend="analytic",
                                               replay_path=None).spec_hash()

    def test_schema_validation(self):
        with pytest.raises(ValueError, match="schema"):
            ReplayBackend({"schema": "bogus/v9", "prefill": [],
                           "decode": []})
        good = json.load(open(FIXTURE))
        with pytest.raises(ValueError, match="no 'prefill' samples"):
            ReplayBackend({**good, "prefill": []})
        bad = {**good, "decode": [{"batch": 1, "latency_s": 0.1}]}
        with pytest.raises(ValueError, match="missing"):
            ReplayBackend(bad)
        no_idle = {k: v for k, v in good.items() if k != "idle_power_w"}
        with pytest.raises(ValueError, match="idle_power_w"):
            ReplayBackend(no_idle)

    def test_recording_without_idle_gaps_exports_device_idle(self):
        """A saturated recording (no gaps) must not export 0 W idle —
        it falls back to the inner backend's device states."""
        rec = RecordingBackend(AnalyticBackend(LLAMA8B))
        reqs = [Request(req_id=i, prompt=None, prompt_len=64,
                        max_new_tokens=4, arrival_time=0.0)
                for i in range(4)]
        ServeEngine(LLAMA8B, backend=rec, batch_policy=SlotCountPolicy(max_batch=4)).run(reqs)
        trace = rec.to_trace()
        assert trace["idle_power_w"] == H100_SXM.idle_power
        assert trace["gated_power_w"] == H100_SXM.gated_power

    def test_replay_specs_never_memoized(self, tmp_path):
        """Re-recording a trace file must re-run the spec — the spec
        hash cannot see trace content, so run_spec refuses to cache."""
        from repro.sweep import run_spec
        rec = RecordingBackend(AnalyticBackend(LLAMA8B))
        ServeEngine(LLAMA8B, backend=rec, batch_policy=SlotCountPolicy(max_batch=4)).run(_reqs(8))
        path = str(tmp_path / "trace.json")
        trace = rec.dump(path)
        spec = ExperimentSpec(model="llama-3.1-8b", backend="replay",
                              replay_path=path, n_requests=8,
                              max_batch=4)
        first, hit1 = run_spec(spec, cache_dir=str(tmp_path / "cc"))
        # re-record with doubled power: same path, new content
        for s in trace["prefill"] + trace["decode"]:
            s["power_w"] *= 2.0
        with open(path, "w") as f:
            json.dump(trace, f)
        second, hit2 = run_spec(spec, cache_dir=str(tmp_path / "cc"))
        assert not hit1 and not hit2
        assert second.busy_energy_j == pytest.approx(
            2 * first.busy_energy_j, rel=1e-6)

    def test_recording_forwards_cost_identity(self):
        scaled = H100_SXM.with_freq_scale(0.5)
        inner = AnalyticBackend(LLAMA8B, device=scaled)
        rec = RecordingBackend(inner)
        eng = ServeEngine(LLAMA8B, backend=rec, batch_policy=SlotCountPolicy(max_batch=4))
        # routers/schedulers must price with the inner backend's device
        assert eng.device is scaled
        assert eng.energy is inner.energy

    def test_recording_emits_valid_schema(self, tmp_path):
        rec = RecordingBackend(AnalyticBackend(LLAMA8B))
        ServeEngine(LLAMA8B, backend=rec, batch_policy=SlotCountPolicy(max_batch=4)).run(_reqs(8))
        trace = rec.dump(str(tmp_path / "t.json"), device="h100-sxm")
        assert trace["schema"] == REPLAY_SCHEMA
        assert trace["prefill"] and trace["decode"]
        assert trace["idle_power_w"] == H100_SXM.idle_power
        ReplayBackend.from_json(str(tmp_path / "t.json"))  # must load


# ---------------------------------------------------------------------------
# executed backend == legacy execute=True
# ---------------------------------------------------------------------------
class TestExecuted:
    def _setup(self):
        import jax
        from repro.models import build_model
        cfg = get_config("stablelm-1.6b").reduced()
        m = build_model(cfg, fmt="float32")
        return cfg, m, m.init(jax.random.PRNGKey(0))

    def _prompts(self, cfg, n=4, seed=0):
        rng = np.random.default_rng(seed)
        return [Request(req_id=i,
                        prompt=rng.integers(0, cfg.vocab_size, 8)
                        .astype(np.int32),
                        prompt_len=8, max_new_tokens=4, arrival_time=0.0)
                for i in range(n)]

    def test_backend_axis_spelling_runs_end_to_end(self):
        """backend="executed" must behave like execute=True, including
        prompt materialization in spec.requests()."""
        spec = ExperimentSpec(model="stablelm-1.6b", backend="executed",
                              reduced=True, fmt="float32", n_requests=3,
                              max_batch=4, buf_len=32,
                              prompt_range=(4, 8), output_range=(2, 4))
        assert all(r.prompt is not None for r in spec.requests())
        res = spec.run()
        assert all(len(r.generated) == r.max_new_tokens
                   for r in res.report.requests)

    def test_execute_conflicts_with_foreign_backend(self):
        with pytest.raises(ValueError, match="conflicts"):
            ServeEngine(LLAMA8B, execute=True,
                        backend=AnalyticBackend(LLAMA8B))

    def test_cache_slot_insert_evict_helpers(self):
        import jax.numpy as jnp
        from repro.batching.continuous import (evict_cache_slot,
                                               insert_cache_slot)
        cache = {"k": jnp.zeros((2, 4, 8)), "pos": jnp.zeros((4,))}
        pcache = {"k": jnp.ones((2, 3, 8)), "pos": 5 * jnp.ones((3,))}
        cache = insert_cache_slot(cache, pcache, row=1, slot=2)
        assert float(cache["k"][0, 2, 0]) == 1.0
        assert float(cache["pos"][2]) == 5.0
        assert float(cache["k"][0, 0, 0]) == 0.0    # other slots intact
        cache = evict_cache_slot(cache, slot=2)
        assert float(cache["k"][0, 2, 0]) == 0.0
        assert float(cache["pos"][2]) == 0.0

    def test_backend_kwarg_matches_legacy_execute(self):
        cfg, m, params = self._setup()
        legacy = ServeEngine(cfg, fmt="float32", mode="continuous",
                             execute=True, model=m, params=params,
                             buf_len=32, batch_policy=SlotCountPolicy(max_batch=4, max_prefill_batch=2))
        rep_a = legacy.run(self._prompts(cfg))
        assert isinstance(legacy.backend, ExecutedBackend)
        explicit = ServeEngine(
            cfg, fmt="float32", mode="continuous",
            backend=ExecutedBackend(cfg, m, params, max_batch=4,
                                    buf_len=32, fmt="float32"), batch_policy=SlotCountPolicy(max_batch=4, max_prefill_batch=2))
        rep_b = explicit.run(self._prompts(cfg))
        assert explicit.execute
        # identical analytic clocks AND identical real generations
        assert rep_a.total_energy_j == rep_b.total_energy_j
        assert rep_a.wall_time_s == rep_b.wall_time_s
        assert ([r.generated for r in rep_a.requests]
                == [r.generated for r in rep_b.requests])
        assert all(len(r.generated) == r.max_new_tokens
                   for r in rep_b.requests)

    # served by the decode step that wrote each token into a copy of the
    # cache before attending over it (not donated); 8 + 12 tokens wrap
    # the 16-slot ring
    SERVED_IDS = [
        [287, 411, 234, 300, 352, 353, 105, 234, 300, 264, 40, 352],
        [297, 296, 297, 83, 296, 296, 296, 302, 31, 183, 161, 191],
        [6, 337, 100, 189, 488, 28, 399, 506, 467, 330, 195, 311],
        [304, 439, 207, 79, 455, 438, 209, 225, 298, 449, 400, 9],
        [251, 265, 318, 500, 477, 348, 25, 450, 123, 504, 33, 378],
        [184, 433, 501, 501, 501, 100, 315, 433, 51, 184, 433, 306]]

    def test_decode_donates_the_cache_and_serves_the_same_ids(self):
        import jax
        cfg, m, params = self._setup()
        rng = np.random.default_rng(3)
        reqs = [Request(req_id=i, prompt=rng.integers(0, cfg.vocab_size, 8)
                        .astype(np.int32), prompt_len=8, max_new_tokens=12,
                        arrival_time=0.0) for i in range(6)]
        backend = ExecutedBackend(cfg, m, params, max_batch=4, buf_len=16,
                                  fmt="float32")
        eng = ServeEngine(cfg, fmt="float32", mode="continuous",
                          backend=backend, batch_policy=SlotCountPolicy(
                              max_batch=4, max_prefill_batch=2))
        rep = eng.run(reqs)
        assert [r.generated for r in rep.requests] == self.SERVED_IDS
        old = backend.cache
        backend.decode_step(DecodeBatch(slots=[0], requests=[reqs[0]],
                                        cache_lens=[20]))
        assert all(a.is_deleted() for a in jax.tree.leaves(old))
        assert not any(a.is_deleted()
                       for a in jax.tree.leaves(backend.cache))


# ---------------------------------------------------------------------------
# DVFS device states
# ---------------------------------------------------------------------------
class TestDVFS:
    def test_scaling_laws(self):
        d = H100_SXM.with_freq_scale(0.7)
        assert d.freq_scale == 0.7
        assert d.peak_flops_16 == pytest.approx(
            H100_SXM.peak_flops_16 * 0.7)
        # dynamic power scales ~f^3 above the static (idle) floor
        expect = (H100_SXM.idle_power
                  + (H100_SXM.power_memory - H100_SXM.idle_power)
                  * 0.7 ** 3)
        assert d.power_memory == pytest.approx(expect)
        # HBM domain, host overhead and non-serving states unchanged
        assert d.hbm_bw == H100_SXM.hbm_bw
        assert d.idle_power == H100_SXM.idle_power
        assert d.gated_power == H100_SXM.gated_power
        assert d.launch_overhead_fused == H100_SXM.launch_overhead_fused

    def test_identity_and_errors(self):
        assert H100_SXM.with_freq_scale(1.0) is H100_SXM
        scaled = H100_SXM.with_freq_scale(0.5)
        assert scaled.with_freq_scale(1.0) is scaled
        with pytest.raises(ValueError, match="positive"):
            H100_SXM.with_freq_scale(0.0)
        with pytest.raises(ValueError, match="outside"):
            TPU_V5E.with_freq_scale(0.01)
        with pytest.raises(ValueError, match="outside"):
            # the *combined* scale is bounds-checked, not the step
            H100_SXM.with_freq_scale(0.5).with_freq_scale(0.15)

    def test_composition_is_multiplicative_and_exact(self):
        """Repeated application composes: scaling by a then b lands on
        the same operating point as scaling once by a*b — so a DVFS
        controller re-targeting a live device never accumulates
        drift."""
        once = H100_SXM.with_freq_scale(0.4)
        twice = H100_SXM.with_freq_scale(0.8).with_freq_scale(0.5)
        assert twice.freq_scale == pytest.approx(0.4)
        assert twice.name == once.name == "h100-sxm@f0.4"
        for f in ("peak_flops_16", "power_memory", "power_mxu",
                  "power_scalar", "hbm_bw", "idle_power", "gated_power"):
            assert getattr(twice, f) == pytest.approx(
                getattr(once, f), rel=1e-12), f
        # and it round-trips back up: 0.4 -> 1.0 via a 2.5x step
        back = twice.with_freq_scale(2.5)
        assert back.freq_scale == pytest.approx(1.0)
        assert back.power_memory == pytest.approx(
            H100_SXM.power_memory, rel=1e-12)

    def test_power_states_table(self):
        states = H100_SXM.power_states()
        assert states["idle"].power_w == H100_SXM.idle_power
        assert states["gated"].wake_latency_s == H100_SXM.wake_latency_s
        assert states["active"].serves and not states["idle"].serves
        with pytest.raises(ValueError, match="no nominal power"):
            H100_SXM.state_power("active")

    def test_downclock_wins_memory_bound_decode(self):
        """The paper-level claim: in the memory-bound decode regime a
        sub-nominal frequency point beats nominal on Wh/request."""
        base = ExperimentSpec(model="llama-3.1-8b", max_batch=32,
                              n_requests=32, prompt_range=(200, 600),
                              output_range=(150, 300))
        nominal = base.run().mean_energy_wh
        slow = base.derive(freq_scale=0.6).run().mean_energy_wh
        assert slow < nominal

    def test_freq_scale_threads_to_all_layers(self):
        spec = ExperimentSpec(model="llama-3.1-8b", freq_scale=0.8)
        assert spec.device_spec().freq_scale == 0.8
        eng = spec.build_engine()
        assert eng.device.freq_scale == 0.8
        assert eng.backend.device.freq_scale == 0.8
        assert eng.energy.device.freq_scale == 0.8


# ---------------------------------------------------------------------------
# spec-hash stability + serialization of the new axes
# ---------------------------------------------------------------------------
class TestSpecAxes:
    def test_defaults_keep_old_hashes(self):
        """Default-valued new fields must not appear in the canonical
        JSON, so every pre-existing spec hash survives the release."""
        d = ExperimentSpec(model="llama-3.1-8b").to_dict()
        assert "backend" not in d
        assert "freq_scale" not in d
        assert "replay_path" not in d

    @pytest.mark.parametrize("changes", [
        {"freq_scale": 0.75},
        {"backend": "replay", "replay_path": FIXTURE},
    ])
    def test_off_default_round_trips(self, changes):
        spec = ExperimentSpec(model="llama-3.1-8b", **changes)
        clone = ExperimentSpec.from_json(spec.to_json())
        assert clone == spec
        assert clone.spec_hash() == spec.spec_hash()
        assert (spec.spec_hash()
                != ExperimentSpec(model="llama-3.1-8b").spec_hash())

    def test_validation(self):
        with pytest.raises(ValueError, match="unknown backend"):
            ExperimentSpec(backend="nvml")
        with pytest.raises(ValueError, match="freq_scale"):
            ExperimentSpec(freq_scale=0.01)
        with pytest.raises(ValueError, match="replay_path"):
            ExperimentSpec(backend="replay")
        with pytest.raises(ValueError, match="did you mean"):
            ExperimentSpec(replay_path=FIXTURE)
        with pytest.raises(ValueError, match="conflict"):
            ExperimentSpec(backend="replay", replay_path=FIXTURE,
                           execute=True)
        with pytest.raises(ValueError, match="profile"):
            ExperimentSpec(pipeline="profile", backend="replay",
                           replay_path=FIXTURE)
        with pytest.raises(ValueError, match="analytic backends only"):
            ExperimentSpec(pipeline="profile", backend="executed")
        with pytest.raises(ValueError, match="analytic backends only"):
            ExperimentSpec(pipeline="profile", execute=True)
        with pytest.raises(ValueError, match="no effect on replayed"):
            ExperimentSpec(backend="replay", replay_path=FIXTURE,
                           freq_scale=0.5)

    def test_engine_kwargs_cannot_contradict_backend(self):
        with pytest.raises(ValueError, match="conflicts with the "
                                             "backend's device"):
            ServeEngine(LLAMA8B, device=TPU_V5E,
                        backend=AnalyticBackend(LLAMA8B))
        with pytest.raises(ValueError, match="precision policy"):
            ServeEngine(LLAMA8B, fmt="int8",
                        backend=AnalyticBackend(LLAMA8B))
        # matching kwargs (or defaults) stay accepted
        ServeEngine(LLAMA8B, fmt="int8",
                    backend=AnalyticBackend(LLAMA8B, fmt="int8"))
        ServeEngine(LLAMA8B, backend=AnalyticBackend(LLAMA8B))
        # equal-but-distinct DeviceSpec objects are NOT a conflict
        ServeEngine(LLAMA8B, device=H100_SXM.with_freq_scale(0.8),
                    backend=AnalyticBackend(
                        LLAMA8B, device=H100_SXM.with_freq_scale(0.8)))


# ---------------------------------------------------------------------------
# ServeReport guards (satellite: tokens_per_s over completed only)
# ---------------------------------------------------------------------------
class TestReportGuards:
    def test_empty_run_all_aggregates_finite(self):
        rep = ServeEngine(LLAMA8B, batch_policy=SlotCountPolicy(max_batch=4)).run([])
        assert rep.tokens_per_s == 0.0
        assert rep.mean_energy_per_request_wh == 0.0
        for v in rep.summary().values():
            assert np.isfinite(v)

    def test_tokens_per_s_counts_completed_only(self):
        done = _reqs(2, out=4)
        for r in done:
            r.tokens_generated = 4
            r.t_done = 1.0
        stuck = _reqs(1, out=4)[0]
        stuck.tokens_generated = 2        # never finished
        rep = ServeReport(requests=done + [stuck], total_energy_j=1.0,
                          busy_energy_j=1.0, idle_energy_j=0.0,
                          wall_time_s=2.0, busy_time_s=2.0,
                          mean_batch=1.0)
        assert rep.tokens_per_s == pytest.approx(8 / 2.0)

"""The executed serving path hides neither its device nor its
precision: quantized specs really quantize, executed specs are never
answered from the spec cache or sent to a process pool, a TPU run bills
only the chip it runs on, and the launcher and compile-cache helper
behave as documented. All on the CPU, at the reduced config."""
import concurrent.futures
import os

import jax
import pytest

from repro.api import ExperimentSpec
from repro.core.hardware import (DEVICE_KINDS, H100_SXM, TPU_V5E,
                                 check_executed_device, device_for_kind)
from repro.launch import compile_cache, serve
from repro.models import build_model
from repro.quant.int8 import Int8Weight
from repro.quant.nf4 import NF4Weight
from repro.serving.backend import ExecutedBackend
from repro.sweep import _atomic_write_json, _cache_path, _code_version, \
    run_spec, sweep


def _spec(**kw) -> ExperimentSpec:
    base = dict(model="stablelm-1.6b", backend="executed", reduced=True,
                fmt="float32", n_requests=2, max_batch=2, buf_len=32,
                prompt_range=(4, 8), output_range=(2, 3))
    return ExperimentSpec(**{**base, **kw})


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"


# ---------------------------------------------------------------------------
# precision: the quantized axis is really quantized
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt,wtype", [("int8", Int8Weight),
                                       ("nf4", NF4Weight)])
def test_quantized_executed_spec_quantizes_weights(fmt, wtype):
    backend = _spec(fmt=fmt).build_engine().backend
    assert backend.model.policy.fmt == fmt
    leaves = jax.tree.leaves(backend.params,
                             is_leaf=lambda x: isinstance(x, wtype))
    assert sum(isinstance(leaf, wtype) for leaf in leaves) == 7
    # off a TPU the model takes the pure-jnp path
    assert not backend.model.policy.use_pallas_kernels


def test_quantized_spec_serves_every_request():
    res = _spec(fmt="int8").run()
    assert all(len(r.generated) == r.max_new_tokens
               for r in res.report.requests)


def test_tpu_run_refuses_the_jnp_reference_path(monkeypatch):
    """A quantized model built without the kernel is refused on a TPU."""
    cfg = _spec().model_config()
    model = build_model(cfg, fmt="int8")
    params = model.quantize(model.init(jax.random.PRNGKey(0)))
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    with pytest.raises(ValueError, match="compiled quant_matmul kernel"):
        ExecutedBackend(cfg, model, params, max_batch=2, buf_len=32,
                        device=TPU_V5E, fmt="int8")


def test_tpu_spec_builds_the_kernel_path(monkeypatch):
    """The executed entry picks the compiled kernel when it runs on a
    TPU (nothing is served here: the fake device runs no step)."""
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    backend = _spec(fmt="int8", device="tpu-v5e").build_engine().backend
    assert backend.model.policy.use_pallas_kernels


# ---------------------------------------------------------------------------
# device: a TPU run bills the chip it runs on
# ---------------------------------------------------------------------------
def test_device_kind_table():
    assert device_for_kind("TPU v5 lite") is TPU_V5E
    assert set(DEVICE_KINDS.values()) == {TPU_V5E}
    with pytest.raises(ValueError, match="no DeviceSpec for device_kind"):
        device_for_kind("TPU v9 imaginary")


def test_check_executed_device():
    check_executed_device(TPU_V5E, "tpu", "TPU v5 lite")
    check_executed_device(TPU_V5E.with_freq_scale(0.5), "tpu",
                          "TPU v5 lite")
    check_executed_device(H100_SXM, "cpu", "cpu")   # CPU runs unchecked
    with pytest.raises(ValueError, match="h100-sxm"):
        check_executed_device(H100_SXM, "tpu", "TPU v5 lite")
    with pytest.raises(ValueError, match="no DeviceSpec"):
        check_executed_device(TPU_V5E, "tpu", "TPU v9 imaginary")


def test_tpu_run_refuses_the_h100_default(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    with pytest.raises(ValueError, match="this chip is device='tpu-v5e'"):
        _spec().build_engine()


# ---------------------------------------------------------------------------
# sweep: executed specs are never cached and never leave the process
# ---------------------------------------------------------------------------
def test_executed_spec_never_uses_the_spec_cache(tmp_path):
    spec = _spec()
    res, hit = run_spec(spec, cache=True, cache_dir=str(tmp_path))
    assert not hit and not os.listdir(tmp_path)
    # a record planted at the spec's cache path is not served
    stale = res.to_dict()
    stale["tokens_per_s"] = -1.0
    _atomic_write_json({"version": _code_version(), "spec": spec.to_dict(),
                        "result": stale},
                       _cache_path(spec, str(tmp_path)))
    res2, hit2 = run_spec(spec, cache=True, cache_dir=str(tmp_path))
    assert not hit2 and res2.tokens_per_s == res.tokens_per_s


def test_sweep_workers_run_executed_points_in_process(tmp_path,
                                                      monkeypatch):
    def no_pool(*a, **kw):
        raise AssertionError("an executed spec was sent to a pool")
    monkeypatch.setattr(concurrent.futures, "ProcessPoolExecutor", no_pool)
    res = sweep(_spec(), {"seed": [0, 1]}, workers=2,
                cache_dir=str(tmp_path))
    assert len(res.results) == 2 and res.cache_hits == 0
    assert all(r.n_requests == 2 for r in res.results.values())


# ---------------------------------------------------------------------------
# compile cache: placed from outside, never on import
# ---------------------------------------------------------------------------
def test_compile_cache_env_wins(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]


# ---------------------------------------------------------------------------
# launcher
# ---------------------------------------------------------------------------
def test_launcher_serves_reduced_config(monkeypatch, capsys):
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: None)
    res = serve.main(["--reduced", "--fmt", "float32", "--n", "3",
                      "--pattern", "poisson"])
    reqs = res.report.requests
    assert len(reqs) == 3 and len(res.report.completed) == 3
    assert all(len(r.generated) == r.max_new_tokens for r in reqs)
    assert "tokens_per_s" in capsys.readouterr().out


def test_launcher_sim_mode_runs_the_full_config():
    res = serve.main(["--sim", "--n", "4"])
    assert res.n_requests == 4 and res.report.requests[0].prompt is None


def test_launcher_sim_mode_uses_the_paper_workload():
    spec = serve.build_spec(["--sim"])
    paper = ExperimentSpec(model="stablelm-1.6b")
    assert spec.backend == "analytic"
    assert spec.prompt_range == paper.prompt_range
    assert spec.output_range == paper.output_range
    assert spec.buf_len == paper.buf_len


@pytest.mark.parametrize("argv,mode", [([], "full"),
                                       (["--reduced"], "reduced")])
def test_launcher_executed_shapes(argv, mode):
    spec = serve.build_spec(argv)
    prompts, outputs, buf_len = serve.EXECUTED_SHAPES[mode]
    assert spec.backend == "executed" and spec.reduced == (mode == "reduced")
    assert (spec.prompt_range, spec.output_range, spec.buf_len) == (
        prompts, outputs, buf_len)
    assert spec.device == ExperimentSpec(model="stablelm-1.6b").device


def test_launcher_bills_the_tpu_it_runs_on(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    assert serve.build_spec([]).device == "tpu-v5e"


def test_launcher_has_no_dry_mode():
    with pytest.raises(SystemExit):
        serve.main(["--dry"])


def test_chip_smoke_refuses_to_run_off_a_tpu():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "chip_smoke.py")
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    with pytest.raises(SystemExit, match="no TPU found"):
        smoke.main()

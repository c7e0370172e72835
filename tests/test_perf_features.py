"""Tests for the §Perf hillclimb features: shard_map expert-parallel
MoE (H1/H2) and the int8 KV cache (H3)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model


class TestKVQuantCache:
    def _models(self, arch="minitron-8b"):
        cfg = get_config(arch).reduced()
        m = build_model(cfg, fmt="float32")
        mq = build_model(cfg, fmt="float32", kv_quant=True)
        params = m.init(jax.random.PRNGKey(0))
        return cfg, m, mq, params

    def test_cache_dtype_and_scales(self):
        cfg, m, mq, params = self._models()
        c = mq.init_cache(2, 16)
        assert c["k"].dtype == jnp.int8
        # head-major: (L, B, Kv, W, hd) codes, one scale per (L, B, Kv, W)
        L, Kv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
        assert c["k"].shape == (L, 2, Kv, 16, hd)
        assert c["k_scale"].shape == (L, 2, Kv, 16)

    def test_decode_close_to_fp_cache(self):
        cfg, m, mq, params = self._models()
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                  cfg.vocab_size)
        lg, c = m.prefill(params, {"tokens": toks}, buf_len=24)
        lgq, cq = mq.prefill(params, {"tokens": toks}, buf_len=24)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(lgq))
        nxt = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
        for _ in range(4):
            lg, c = m.decode_step(params, nxt, c)
            lgq, cq = mq.decode_step(params, nxt, cq)
            rel = float(jnp.max(jnp.abs(lg - lgq))
                        / (jnp.max(jnp.abs(lg)) + 1e-9))
            assert rel < 0.05
            assert bool((jnp.argmax(lg, -1) == jnp.argmax(lgq, -1)).all())
            nxt = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)

    @pytest.mark.parametrize("arch", ["granite-moe-1b-a400m",
                                      "seamless-m4t-large-v2"])
    def test_other_families(self, arch):
        cfg, m, mq, params = self._models(arch)
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks}
        if cfg.family == "audio":
            batch["frames"] = jnp.ones(
                (2, 2, cfg.d_model), jnp.bfloat16) * 0.1
        lg, c = m.prefill(params, batch, buf_len=16)
        lgq, cq = mq.prefill(params, batch, buf_len=16)
        nxt = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
        lg, _ = m.decode_step(params, nxt, c)
        lgq, _ = mq.decode_step(params, nxt, cq)
        assert float(jnp.max(jnp.abs(lg - lgq))
                     / (jnp.max(jnp.abs(lg)) + 1e-9)) < 0.08


def test_expert_parallel_matches_local_subprocess():
    """shard_map expert-parallel MoE == local sort/scatter MoE on an
    8-device host mesh (numerical equivalence of H1's optimization)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
import numpy as np
from repro.configs import get_config
from repro.models import build_model, moe as moe_mod
from repro.models import moe
from repro.launch import sharding as sh

mesh = jax.make_mesh((2, 4), ("data", "model"))
cfg = get_config("granite-moe-1b-a400m").reduced()
m = build_model(cfg, fmt="float32")
params = m.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 8), 0, cfg.vocab_size)

def fwd(p, t):
    h, aux = m.forward_train(p, {"tokens": t})
    return m.logits(p, h[:, -1])

ref = jax.jit(fwd)(params, toks)          # local MoE path
with mesh, moe.expert_parallel(mesh, data_axes=("data",)):
    got = jax.jit(fwd)(params, toks)      # shard_map EP path
err = float(jnp.max(jnp.abs(ref - got)))
assert err < 2e-4, f"mismatch {err}"
print("EP_MATCH_OK", err)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    # hermetic CPU child: a jax-initialized parent exports
    # TPU_LIBRARY_PATH (libtpu ships in the image), and a child that
    # inherits it without JAX_PLATFORMS blocks trying to grab a TPU
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("TPU_LIBRARY_PATH", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "EP_MATCH_OK" in out.stdout, out.stderr[-2500:]

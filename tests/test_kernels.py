"""Pallas kernel tests: shape/dtype sweeps, interpret=True vs the
pure-jnp ref.py oracle (assignment requirement c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.quant import quantize_int8, quantize_nf4
from repro.kernels.quant_matmul.kernel import (int8_matmul_pallas,
                                               nf4_matmul_pallas)
from repro.kernels.quant_matmul import ops as qops
from repro.kernels.quant_matmul.ref import (int8_matmul_ref,
                                            nf4_matmul_ref)
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.paged_attention.ref import paged_attention_ref


def _rand(shape, seed, dtype=jnp.float32, scale=0.3):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape) * scale
    return x.astype(dtype)


class TestQuantMatmul:
    @pytest.mark.parametrize("m,k,n,bm,bk,bn", [
        (32, 128, 64, 32, 64, 64),
        (64, 256, 128, 32, 128, 64),
        (128, 512, 256, 64, 256, 128),
        (8, 128, 128, 8, 128, 128),
    ])
    def test_int8_shapes(self, m, k, n, bm, bk, bn):
        x = _rand((m, k), 0)
        w = _rand((k, n), 1, scale=0.05)
        q = quantize_int8(w)
        out = int8_matmul_pallas(x, q.codes, q.scale, bm=bm, bn=bn, bk=bk,
                                 compute_dtype=jnp.float32, interpret=True)
        ref = int8_matmul_ref(x, q.codes, q.scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                           (jnp.bfloat16, 2e-2)])
    def test_int8_dtypes(self, dtype, tol):
        x = _rand((32, 256), 0)
        w = _rand((256, 128), 1, scale=0.05)
        q = quantize_int8(w)
        out = int8_matmul_pallas(x, q.codes, q.scale, bm=32, bn=128,
                                 bk=128, compute_dtype=dtype, interpret=True)
        ref = int8_matmul_ref(x, q.codes, q.scale)
        rel = np.abs(np.asarray(out, np.float32) - np.asarray(ref)).max() \
            / (np.abs(np.asarray(ref)).max() + 1e-9)
        assert rel < tol

    @pytest.mark.parametrize("block", [16, 32, 64])
    @pytest.mark.parametrize("m,k,n", [(32, 128, 64), (64, 256, 128)])
    def test_nf4_shapes(self, block, m, k, n):
        x = _rand((m, k), 0)
        w = _rand((k, n), 1, scale=0.05)
        q = quantize_nf4(w, block)
        out = nf4_matmul_pallas(x, q.packed, q.absmax, bm=m, bn=n,
                                bk=min(128, k), compute_dtype=jnp.float32,
                                interpret=True)
        ref = nf4_matmul_ref(x, q.packed, q.absmax)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_ops_wrapper_with_outliers(self):
        x = _rand((4, 16, 128), 0, scale=1.0)        # 3-D input
        w = np.array(_rand((128, 64), 1, scale=0.05))
        w[3] *= 50                                   # force an outlier row
        w = jnp.asarray(w)
        q = quantize_int8(w, outlier_fraction=0.02)
        out = qops.int8_matmul_kernel(x, q, compute_dtype=jnp.float32,
                                       interpret=True)
        ref = jnp.einsum("bsk,kn->bsn", x, w)
        rel = float(jnp.max(jnp.abs(out - ref))
                    / (jnp.max(jnp.abs(ref)) + 1e-9))
        assert out.shape == (4, 16, 64)
        assert rel < 0.02

    def test_nf4_ops_wrapper(self):
        x = _rand((2, 8, 128), 0, scale=1.0)
        w = _rand((128, 64), 1, scale=0.05)
        q = quantize_nf4(w, 64)
        out = qops.nf4_matmul_kernel(x, q, compute_dtype=jnp.float32,
                                      interpret=True)
        ref = nf4_matmul_ref(x.reshape(-1, 128), q.packed, q.absmax)
        np.testing.assert_allclose(np.asarray(out).reshape(-1, 64),
                                   np.asarray(ref), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("m", [3, 8, 256, 300, 520, 4160])
    def test_row_blocks_pad_little(self, m):
        bm, _, _, mp = qops._pick_blocks(m, 2048, 5632)
        assert bm <= qops.ROW_BLOCK and mp % bm == 0 and mp >= m
        if m <= qops.ROW_BLOCK:
            assert mp == bm and mp - m < 8
        else:
            assert bm % 16 == 0 and mp - m < 16 * (mp // bm)

    @pytest.mark.parametrize("fmt", ["int8", "nf4"])
    def test_ops_wrapper_split_rows(self, fmt):
        """Rows past one block split into padded blocks; the padding is
        sliced off."""
        x = _rand((300, 128), 0, scale=1.0)
        w = _rand((128, 64), 1, scale=0.05)
        if fmt == "int8":
            q = quantize_int8(w)
            out = qops.int8_matmul_kernel(x, q, compute_dtype=jnp.float32,
                                          interpret=True)
            ref = int8_matmul_ref(x, q.codes, q.scale)
        else:
            q = quantize_nf4(w, 64)
            out = qops.nf4_matmul_kernel(x, q, compute_dtype=jnp.float32,
                                         interpret=True)
            ref = nf4_matmul_ref(x, q.packed, q.absmax)
        assert out.shape == (300, 64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("S,bq,bkv", [(128, 64, 64), (256, 64, 128),
                                          (256, 256, 256)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_shapes_causal(self, S, bq, bkv, causal):
        B, H, Kv, d = 2, 4, 2, 64
        q = _rand((B, S, H, d), 0, scale=1.0)
        k = _rand((B, S, Kv, d), 1, scale=1.0)
        v = _rand((B, S, Kv, d), 2, scale=1.0)
        out = flash_attention_pallas(q, k, v, causal=causal, bq=bq,
                                     bkv=bkv, interpret=True)
        ref = attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [32, 64, 100])
    def test_sliding_window(self, window):
        B, S, H, Kv, d = 1, 256, 4, 4, 32
        q = _rand((B, S, H, d), 0, scale=1.0)
        k = _rand((B, S, Kv, d), 1, scale=1.0)
        v = _rand((B, S, Kv, d), 2, scale=1.0)
        out = flash_attention_pallas(q, k, v, causal=True, window=window,
                                     bq=64, bkv=64, interpret=True)
        ref = attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_gqa_groups(self):
        """H != Kv exercises the kv index_map group arithmetic."""
        B, S, H, Kv, d = 2, 128, 8, 2, 32
        q = _rand((B, S, H, d), 0, scale=1.0)
        k = _rand((B, S, Kv, d), 1, scale=1.0)
        v = _rand((B, S, Kv, d), 2, scale=1.0)
        out = flash_attention_pallas(q, k, v, bq=64, bkv=64,
                                     interpret=True)
        ref = attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        B, S, H, Kv, d = 1, 128, 4, 2, 64
        q = _rand((B, S, H, d), 0, jnp.bfloat16, 1.0)
        k = _rand((B, S, Kv, d), 1, jnp.bfloat16, 1.0)
        v = _rand((B, S, Kv, d), 2, jnp.bfloat16, 1.0)
        out = flash_attention_pallas(q, k, v, bq=64, bkv=64,
                                     interpret=True)
        ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32))
        assert float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref))) < 0.05


class TestPagedAttention:
    def _pool(self, n_pool, page, Kv, d, seed=0):
        return (_rand((n_pool, page, Kv, d), seed, scale=1.0),
                _rand((n_pool, page, Kv, d), seed + 1, scale=1.0))

    @pytest.mark.parametrize("page", [16, 32, 128])
    def test_page_sizes(self, page):
        n_pool, B, H, Kv, d = 12, 2, 8, 2, 64
        kp, vp = self._pool(n_pool, page, Kv, d)
        q = _rand((B, H, d), 5, scale=1.0)
        pt = jnp.array([[0, 1, 2], [3, 4, -1]], jnp.int32)
        sl = jnp.array([2 * page + 3, page + 1], jnp.int32)
        out = paged_attention_pallas(q, kp, vp, pt, sl, interpret=True)
        ref = paged_attention_ref(q, kp, vp, pt, sl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_matches_contiguous_attention(self):
        """Paged result == ordinary decode attention over the gathered
        cache (cross-oracle check against flash ref)."""
        page, n_pool, B, H, Kv, d = 32, 8, 2, 4, 4, 32
        kp, vp = self._pool(n_pool, page, Kv, d)
        q = _rand((B, H, d), 9, scale=1.0)
        pt = jnp.array([[2, 0], [5, -1]], jnp.int32)
        sl = jnp.array([50, 20], jnp.int32)
        out = paged_attention_pallas(q, kp, vp, pt, sl, interpret=True)
        # build contiguous caches and use the flash oracle (q len 1)
        for b in range(B):
            pages = [p for p in np.asarray(pt[b]) if p >= 0]
            kc = jnp.concatenate([kp[p] for p in pages], 0)[:int(sl[b])]
            vc = jnp.concatenate([vp[p] for p in pages], 0)[:int(sl[b])]
            ref = attention_ref(q[b:b + 1, None], kc[None], vc[None],
                                causal=False)
            np.testing.assert_allclose(np.asarray(out[b]),
                                       np.asarray(ref[0, 0]),
                                       rtol=2e-5, atol=2e-5)

    def test_single_page_and_full_pool(self):
        page, n_pool, B, H, Kv, d = 16, 4, 1, 2, 1, 32
        kp, vp = self._pool(n_pool, page, Kv, d)
        q = _rand((B, H, d), 3, scale=1.0)
        pt = jnp.array([[1]], jnp.int32)
        sl = jnp.array([7], jnp.int32)
        out = paged_attention_pallas(q, kp, vp, pt, sl, interpret=True)
        ref = paged_attention_ref(q, kp, vp, pt, sl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

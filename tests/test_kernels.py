"""Per-kernel allclose vs the pure-jnp oracle (interpret=True on CPU).
Sweeps shapes, dtypes, and mask variants per the deliverable contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kmeans_assign import kmeans_assign_pallas
from repro.kernels.logreg_grad import logreg_grad_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas

RNG = np.random.default_rng(42)


def _rand(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


def _tol(dtype):
    return dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,H,KV,Sq,Sk,hd", [
        (1, 4, 4, 128, 128, 64),      # MHA square
        (2, 4, 2, 128, 128, 64),      # GQA
        (1, 8, 1, 128, 512, 128),     # MQA rectangular (decode-ish)
        (2, 2, 2, 256, 256, 32),      # small head dim
    ])
    def test_causal_sweep(self, B, H, KV, Sq, Sk, hd, dtype):
        q = _rand((B, H, Sq, hd), dtype)
        k = _rand((B, KV, Sk, hd), dtype)
        v = _rand((B, KV, Sk, hd), dtype)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        expect = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32), **_tol(dtype))

    @pytest.mark.parametrize("mask_kw", [
        dict(causal=False),
        dict(causal=True, window=100),
        dict(causal=True, window=128),
        dict(causal=True, chunk=128),
        dict(causal=True, chunk=256),
    ])
    def test_mask_variants(self, mask_kw):
        q = _rand((1, 4, 256, 64), jnp.float32)
        k = _rand((1, 2, 256, 64), jnp.float32)
        v = _rand((1, 2, 256, 64), jnp.float32)
        out = flash_attention(q, k, v, interpret=True, **mask_kw)
        expect = ref.flash_attention_ref(q, k, v, **mask_kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=2e-4, atol=2e-4)

    def test_block_shape_independence(self):
        """Different VMEM tilings must give identical results."""
        q = _rand((1, 2, 256, 64), jnp.float32)
        k = _rand((1, 2, 256, 64), jnp.float32)
        v = _rand((1, 2, 256, 64), jnp.float32)
        outs = [np.asarray(flash_attention(q, k, v, causal=True, block_q=bq,
                                           block_k=bk, interpret=True))
                for bq, bk in [(128, 128), (64, 128), (128, 64), (256, 256)]]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)

    def test_fully_masked_rows_are_zero(self):
        """Rows whose window admits no keys must not NaN (0/denom guard)."""
        q = _rand((1, 1, 128, 64), jnp.float32)
        k = _rand((1, 1, 128, 64), jnp.float32)
        v = _rand((1, 1, 128, 64), jnp.float32)
        out = flash_attention(q, k, v, causal=True, window=1, interpret=True)
        assert bool(jnp.isfinite(out).all())


class TestLogregGrad:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n,d", [(256, 512), (512, 1024), (1024, 512),
                                     (256, 2048)])
    def test_sweep(self, n, d, dtype):
        X = _rand((n, d), dtype)
        y = jnp.asarray(RNG.integers(0, 2, size=n), jnp.float32)
        w = (_rand((d,), dtype) * 0.05).astype(dtype)
        got = logreg_grad_pallas(X, y, w, interpret=True)
        expect = ref.logreg_grad_ref(X, y, w)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(expect, np.float32),
                                   rtol=5e-2 if dtype == jnp.bfloat16 else 2e-3,
                                   atol=5e-1 if dtype == jnp.bfloat16 else 5e-2)

    def test_block_shape_independence(self):
        X = _rand((512, 1024), jnp.float32)
        y = jnp.asarray(RNG.integers(0, 2, size=512), jnp.float32)
        w = _rand((1024,), jnp.float32) * 0.05
        outs = [np.asarray(logreg_grad_pallas(X, y, w, block_rows=br,
                                              block_cols=bc, interpret=True))
                for br, bc in [(256, 512), (128, 256), (512, 1024)]]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], rtol=1e-4, atol=1e-3)


class TestRMSNorm:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape", [(4, 33, 256), (128, 1024), (2, 7, 8, 512)])
    def test_sweep(self, shape, dtype):
        x = _rand(shape, dtype)
        w = _rand((shape[-1],), dtype)
        got = rmsnorm_pallas(x, w, interpret=True)
        expect = ref.rmsnorm_ref(x, w)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(expect, np.float32), **_tol(dtype))

    def test_scale_invariance_of_direction(self):
        """rmsnorm(c·x) == rmsnorm(x) for c>0 — the defining invariant."""
        x = _rand((8, 128), jnp.float32)
        w = jnp.ones((128,), jnp.float32)
        a = rmsnorm_pallas(x, w, interpret=True)
        b = rmsnorm_pallas(x * 7.3, w, interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)


class TestKMeansAssign:
    """Fused pairwise-distance assignment vs its oracle — the oracle uses
    the identical expanded form (||c||² − 2·x·c), so the comparison is
    exact fp parity, not just same-argmin on separated data."""

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("n,d,k", [
        (256, 512, 8),        # single tile
        (512, 1024, 5),       # multi-tile both axes, odd k
        (256, 512, 16),
    ])
    def test_sweep(self, n, d, k, dtype):
        X = _rand((n, d), dtype)
        C = _rand((k, d), dtype)
        got = kmeans_assign_pallas(X, C, interpret=True)
        want = ref.kmeans_assign_ref(X, C)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_matches_full_distance_argmin(self):
        """The expanded form must produce the same assignment as the naive
        (n, k, d) broadcast argmin on generic float data."""
        X = _rand((256, 512), jnp.float32)
        C = _rand((6, 512), jnp.float32)
        got = kmeans_assign_pallas(X, C, interpret=True)
        d2 = jnp.sum((X[:, None, :] - C[None, :, :]) ** 2, axis=-1)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(jnp.argmin(d2, axis=-1)))

    def test_block_shape_independence(self):
        X = _rand((512, 1024), jnp.float32)
        C = _rand((8, 1024), jnp.float32)
        a = kmeans_assign_pallas(X, C, block_rows=256, block_cols=512,
                                 interpret=True)
        b = kmeans_assign_pallas(X, C, block_rows=128, block_cols=256,
                                 interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_tie_breaks_to_lowest_index(self):
        """Duplicate centroids: the fused argmin must keep jnp.argmin's
        first-wins tie rule (the manual iota/min reduction inside the
        kernel exists exactly for this)."""
        X = _rand((256, 512), jnp.float32)
        C0 = _rand((4, 512), jnp.float32)
        C = jnp.concatenate([C0, C0], axis=0)        # every row ties 2-way
        got = kmeans_assign_pallas(X, C, interpret=True)
        want = ref.kmeans_assign_ref(X, C)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(jnp.max(got)) < 4                 # always the first copy

    def test_routed_training_matches_oracle_path(self):
        """KMeansParameters(use_kernel=True) must train bitwise-identical
        centroids to the default path (same assignments → same sums)."""
        from repro.core.algorithms.kmeans import KMeans, KMeansParameters
        from repro.core.numeric_table import MLNumericTable

        rng = np.random.default_rng(0)
        X = (rng.normal(size=(128, 16)).astype(np.float32)
             + np.repeat(np.eye(4, 16, dtype=np.float32) * 6.0, 32, axis=0))
        table = MLNumericTable.from_numpy(X, num_shards=2)
        base = KMeans.train(table, KMeansParameters(k=4, max_iter=5))
        fused = KMeans.train(table, KMeansParameters(k=4, max_iter=5,
                                                     use_kernel=True))
        np.testing.assert_array_equal(np.asarray(base.centroids),
                                      np.asarray(fused.centroids))
        np.testing.assert_array_equal(
            np.asarray(base.predict(jnp.asarray(X))),
            np.asarray(fused.predict(jnp.asarray(X))))


class TestOpsWrappers:
    def test_fallback_on_indivisible_shapes(self):
        from repro.kernels import ops
        q = _rand((1, 2, 100, 64), jnp.float32)   # 100 not divisible by 128
        k = _rand((1, 2, 100, 64), jnp.float32)
        v = _rand((1, 2, 100, 64), jnp.float32)
        out = ops.flash_attention(q, k, v)
        expect = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-4, atol=1e-4)

    def test_shape_validation(self):
        from repro.kernels import ops
        with pytest.raises(ValueError):
            ops.logreg_grad(jnp.zeros((4, 4)), jnp.zeros((5,)), jnp.zeros((4,)))
        with pytest.raises(ValueError):
            ops.rmsnorm(jnp.zeros((4, 8)), jnp.zeros((9,)))
        with pytest.raises(ValueError):
            ops.kmeans_assign(jnp.zeros((8, 4)), jnp.zeros((2, 5)))

    def test_kmeans_assign_fallback_on_indivisible_shapes(self):
        from repro.kernels import ops
        X = _rand((37, 9), jnp.float32)              # tiles nothing
        C = _rand((3, 9), jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(ops.kmeans_assign(X, C)),
            np.asarray(ref.kmeans_assign_ref(X, C)))

    @pytest.fixture
    def on_tpu_interpreted(self, monkeypatch):
        """The wrappers' TPU branches, with the kernels interpreted."""
        from repro.kernels import ops
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
        monkeypatch.setattr(ops, "_interp", lambda: True)
        return ops

    def test_tpu_pads_untileable_shapes_exactly(self, on_tpu_interpreted):
        """On TPU, shapes the blocks cannot tile are zero-padded to the
        tile, never handed to the reference, with the reference's result."""
        ops = on_tpu_interpreted
        X = jnp.abs(_rand((37, 600), jnp.float32))   # 600 cols: 512 blocks
        y = jnp.asarray(RNG.integers(0, 2, size=37), jnp.float32)
        w = _rand((600,), jnp.float32) * 0.05
        np.testing.assert_allclose(
            np.asarray(ops.logreg_grad(X, y, w)),
            np.asarray(ref.logreg_grad_ref(X, y, w)), rtol=2e-4, atol=2e-4)
        Xk = _rand((300, 9), jnp.float32)             # 300 rows: 256 blocks
        C = _rand((3, 9), jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(ops.kmeans_assign(Xk, C)),
            np.asarray(ref.kmeans_assign_ref(Xk, C)))
        xq, xs = ops.quantize_rows(_rand((4, 600), jnp.float32))
        wq = jnp.asarray(RNG.integers(-127, 128, size=(600, 300)), jnp.int8)
        ws = jnp.abs(_rand((300,), jnp.float32)) + 0.01
        np.testing.assert_array_equal(
            np.asarray(ops.quant_matmul(xq, xs, wq, ws)),
            np.asarray(ref.quant_matmul_ref(xq, xs, wq, ws)))

    def test_tpu_raises_where_padding_is_not_exact(self, on_tpu_interpreted):
        ops = on_tpu_interpreted
        q = _rand((1, 2, 200, 64), jnp.float32)       # 200: 128 blocks
        with pytest.raises(ValueError, match="no reference fallback"):
            ops.flash_attention(q, q, q)
        with pytest.raises(ValueError, match="no reference fallback"):
            ops.ssd_chunk_scan(_rand((1, 2, 100), jnp.float32),
                               _rand((1, 2, 100, 8), jnp.float32),
                               _rand((1, 100, 4), jnp.float32),
                               _rand((1, 100, 4), jnp.float32), chunk=64)


class TestSSDChunkScan:
    def _inputs(self, B=2, H=3, S=256, P=16, N=32, seed=0):
        rng = np.random.default_rng(seed)
        log_a = jnp.asarray(-np.abs(rng.normal(size=(B, H, S))) * 0.1,
                            jnp.float32)
        dx = jnp.asarray(rng.normal(size=(B, H, S, P)), jnp.float32)
        Bm = jnp.asarray(rng.normal(size=(B, S, N)) * 0.3, jnp.float32)
        Cm = jnp.asarray(rng.normal(size=(B, S, N)) * 0.3, jnp.float32)
        h0 = jnp.asarray(rng.normal(size=(B, H, P, N)) * 0.1, jnp.float32)
        return log_a, dx, Bm, Cm, h0

    @pytest.mark.parametrize("chunk", [32, 64, 128])
    @pytest.mark.parametrize("shape", [(1, 2, 128, 8, 16), (2, 3, 256, 16, 32)])
    def test_sweep(self, shape, chunk):
        from repro.kernels.ssd_scan import ssd_chunk_scan
        B, H, S, P, N = shape
        log_a, dx, Bm, Cm, h0 = self._inputs(B, H, S, P, N)
        y, h = ssd_chunk_scan(log_a, dx, Bm, Cm, h0, chunk=chunk,
                              interpret=True)
        yr, hr = ref.ssd_chunk_scan_ref(log_a, dx, Bm, Cm, h0, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                                   rtol=1e-4, atol=1e-4)

    def test_chunk_size_independence(self):
        """Different VMEM chunk tilings must agree (the scan is exact)."""
        from repro.kernels.ssd_scan import ssd_chunk_scan
        log_a, dx, Bm, Cm, h0 = self._inputs()
        outs = [np.asarray(ssd_chunk_scan(log_a, dx, Bm, Cm, h0, chunk=c,
                                          interpret=True)[0])
                for c in (32, 64, 256)]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], rtol=1e-3, atol=1e-3)

    def test_zero_decay_is_cumulative_outer_products(self):
        """With a ≡ 1 (log_a = 0) the SSD state is a plain running sum of
        dx⊗B, and y_t = C_t · Σ_{s≤t} dx_s⊗B_s — an analytic invariant."""
        from repro.kernels.ssd_scan import ssd_chunk_scan
        rng = np.random.default_rng(1)
        B, H, S, P, N = 1, 1, 64, 4, 8
        log_a = jnp.zeros((B, H, S), jnp.float32)
        dx = jnp.asarray(rng.normal(size=(B, H, S, P)), jnp.float32)
        Bm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
        Cm = jnp.asarray(rng.normal(size=(B, S, N)), jnp.float32)
        y, h = ssd_chunk_scan(log_a, dx, Bm, Cm, chunk=16, interpret=True)
        run = np.zeros((P, N))
        for t in range(S):
            run = run + np.outer(np.asarray(dx[0, 0, t]), np.asarray(Bm[0, t]))
            expect = run @ np.asarray(Cm[0, t])
            np.testing.assert_allclose(np.asarray(y[0, 0, t]), expect,
                                       rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(h[0, 0]), run, rtol=1e-3,
                                   atol=1e-3)

"""MLNumericTable: matrixBatchMap / reduce semantics, partition invariance
(the paper's core 'batch operation on partitions' contract)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.local_matrix import LocalMatrix
from repro.core.numeric_table import MLNumericTable


def _table(rng, n=16, d=4, shards=4):
    return MLNumericTable.from_numpy(
        np.asarray(rng.normal(size=(n, d)), np.float32), num_shards=shards)


class TestBasics:
    def test_shapes(self, rng):
        t = _table(rng)
        assert t.num_rows == 16 and t.num_cols == 4 and t.rows_per_shard == 4

    def test_indivisible_rejected(self, rng):
        with pytest.raises(ValueError):
            MLNumericTable.from_numpy(np.zeros((10, 2), np.float32), num_shards=3)

    def test_map_rows(self, rng):
        t = _table(rng)
        doubled = t.map_rows(lambda r: r * 2)
        np.testing.assert_allclose(np.asarray(doubled.data),
                                   2 * np.asarray(t.data), rtol=1e-6)


class TestMatrixBatchMap:
    def test_identity(self, rng):
        t = _table(rng)
        out = t.matrix_batch_map(lambda m: m)
        np.testing.assert_allclose(np.asarray(out.data), np.asarray(t.data))

    def test_per_partition_rowsum(self, rng):
        """One output row per partition: the local-stats pattern every MLI
        algorithm uses before a global reduce."""
        t = _table(rng, n=16, shards=4)
        out = t.matrix_batch_map(lambda m: LocalMatrix(jnp.sum(m.data, 0)[None, :]))
        assert out.num_rows == 4
        blocks = np.asarray(t.data).reshape(4, 4, 4)
        np.testing.assert_allclose(np.asarray(out.data), blocks.sum(1), rtol=1e-5)

    def test_broadcast_args(self, rng):
        t = _table(rng)
        w = jnp.ones((4,), jnp.float32)
        out = t.matrix_batch_map(lambda m, ww: LocalMatrix(m.data @ ww[:, None]), w)
        np.testing.assert_allclose(np.asarray(out.data)[:, 0],
                                   np.asarray(t.data).sum(1), rtol=1e-5)

    def test_works_under_jit(self, rng):
        t = _table(rng)

        @jax.jit
        def f(data):
            tt = MLNumericTable(data, num_shards=4)
            return tt.matrix_batch_map(lambda m: m * 2).data

        np.testing.assert_allclose(np.asarray(f(t.data)),
                                   2 * np.asarray(t.data), rtol=1e-6)


class TestReduce:
    def test_reduce_sum_matches_numpy(self, rng):
        t = _table(rng)
        got = t.reduce(jnp.add)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(t.data).sum(0), rtol=1e-4, atol=1e-5)

    def test_reduce_max(self, rng):
        t = _table(rng)
        got = t.reduce(jnp.maximum, identity=jnp.full((4,), -np.inf, jnp.float32))
        np.testing.assert_allclose(np.asarray(got), np.asarray(t.data).max(0), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(n_shards=st.sampled_from([1, 2, 4, 8]), seed=st.integers(0, 2**16))
def test_reduce_shard_invariance_property(n_shards, seed):
    """Global reduce must not depend on the partitioning — the property that
    makes MLI algorithms deterministic across cluster sizes."""
    rng = np.random.default_rng(seed)
    X = np.asarray(rng.normal(size=(16, 3)), np.float32)
    t = MLNumericTable.from_numpy(X, num_shards=n_shards)
    np.testing.assert_allclose(np.asarray(t.reduce(jnp.add)), X.sum(0),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(shards=st.sampled_from([1, 2, 4]), seed=st.integers(0, 2**16))
def test_batchmap_then_concat_property(shards, seed):
    """matrixBatchMap with a row-preserving fn == applying fn globally.

    Partitions run fn compiled, as every chip of a mesh does, so the global
    reference is fn compiled too: XLA may contract ``x * 3 + 1`` into one
    fused multiply-add, which rounds once where NumPy rounds twice."""
    rng = np.random.default_rng(seed)
    X = np.asarray(rng.normal(size=(8, 3)), np.float32)
    t = MLNumericTable.from_numpy(X, num_shards=shards)
    fn = lambda m: LocalMatrix(m.data * 3 + 1)  # noqa: E731
    out = t.matrix_batch_map(fn)
    want = jax.jit(lambda x: fn(LocalMatrix(x)).data)(X)
    np.testing.assert_allclose(np.asarray(out.data), np.asarray(want),
                               rtol=1e-6)

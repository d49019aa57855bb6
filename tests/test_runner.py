"""DistributedRunner: the shared execution layer (docs/architecture.md).

Covers the paper's §IV-A schedule-equivalence claim end to end: all three
CollectiveSchedules must produce identical models (to fp tolerance) for
logistic regression and k-means on a real multi-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``), plus the
partition-layer round-trip property and the runner's emulated-mode
semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import partition as pt
from repro.core.collectives import CollectiveSchedule
from repro.core.numeric_table import MLNumericTable
from repro.core.runner import DistributedRunner
from repro.data import BatchIterator

# --------------------------------------------------------------------------- #
# schedule agreement on a real 8-device mesh (paper §IV-A)
# --------------------------------------------------------------------------- #
_MESH_AGREEMENT_PROGRAM = """
import json
import numpy as np
import jax

from repro.core.compat import make_mesh
from repro.core import MLNumericTable, CollectiveSchedule, DistributedRunner
from repro.core.algorithms.logistic_regression import (
    LogisticRegressionAlgorithm, LogisticRegressionParameters)
from repro.core.algorithms.kmeans import KMeans, KMeansParameters
from repro.core.algorithms.als import (ALSParameters, BroadcastALS,
                                       pack_csr_table)
from repro.data import synth_classification, synth_netflix_tiled

assert len(jax.devices()) == 8, jax.devices()
mesh = make_mesh((8,), ("data",))

X, y, _ = synth_classification(512, 16, seed=0)
data = np.concatenate([y[:, None], X], 1).astype(np.float32)
table = MLNumericTable.from_numpy(data, mesh=mesh)
tX = MLNumericTable.from_numpy(X.astype(np.float32), mesh=mesh)

drift = {}
logreg, kmeans = {}, {}
for sched in CollectiveSchedule:
    p = LogisticRegressionParameters(learning_rate=0.5, max_iter=5,
                                     local_batch_size=16, schedule=sched)
    logreg[sched] = np.asarray(LogisticRegressionAlgorithm.train(table, p).weights)
    kp = KMeansParameters(k=4, max_iter=5, seed=0, schedule=sched)
    kmeans[sched] = np.asarray(KMeans.train(tX, kp).centroids)

# mesh-mode combine="concat": directly (identity map must reassemble the
# table on every schedule) and through ALS (whose factor broadcast rides it)
M = synth_netflix_tiled(users=64, items=48, rank=4, tiles=1, density=0.2)
r, c = np.nonzero(M)
v = M[r, c]
als = {}
for sched in CollectiveSchedule:
    runner = DistributedRunner.for_table(tX, schedule=sched)
    got = runner.partition_apply(tX.data, lambda b: b * 1.0, combine="concat")
    drift["concat_" + sched.value] = float(
        np.abs(np.asarray(got) - X.astype(np.float32)).max())
    d = pack_csr_table(r, c, v, M.shape[0], 32, mesh=mesh)
    dT = pack_csr_table(c, r, v, M.shape[1], 32, mesh=mesh)
    ap = ALSParameters(rank=4, lam=0.05, max_iter=3, seed=0, schedule=sched)
    als[sched] = np.asarray(BroadcastALS.train(d, ap, data_transposed=dT).U)

ref_w = logreg[CollectiveSchedule.ALLREDUCE]
ref_c = kmeans[CollectiveSchedule.ALLREDUCE]
ref_u = als[CollectiveSchedule.ALLREDUCE]
for sched in CollectiveSchedule:
    drift["logreg_" + sched.value] = float(np.abs(logreg[sched] - ref_w).max())
    drift["kmeans_" + sched.value] = float(np.abs(kmeans[sched] - ref_c).max())
    drift["als_" + sched.value] = float(np.abs(als[sched] - ref_u).max())
print("RESULT::" + json.dumps(drift))
"""


def test_schedules_agree_on_8_device_mesh(eight_device_run):
    """All three schedules must train identical logreg, kmeans, and ALS
    models on an 8-way data-parallel mesh — the runner makes the schedule a
    pure wire-pattern knob — and mesh-mode combine="concat" must reassemble
    partitioned rows exactly under every schedule."""
    drift = eight_device_run(_MESH_AGREEMENT_PROGRAM)
    for key, d in drift.items():
        assert d < 1e-5, f"{key}: schedules disagree by {d}"


# --------------------------------------------------------------------------- #
# emulated-mode semantics (always run, one device)
# --------------------------------------------------------------------------- #
class TestRunOnce:
    def test_sum_matches_numpy(self, rng):
        X = np.asarray(rng.normal(size=(32, 5)), np.float32)
        t = MLNumericTable.from_numpy(X, num_shards=4)
        runner = DistributedRunner.for_table(t)
        got = runner.run_once(t, lambda b: jnp.sum(b, axis=0), combine="sum")
        np.testing.assert_allclose(np.asarray(got), X.sum(0), rtol=1e-5)

    def test_mean_matches_numpy(self, rng):
        X = np.asarray(rng.normal(size=(32, 5)), np.float32)
        t = MLNumericTable.from_numpy(X, num_shards=4)
        runner = DistributedRunner.for_table(t)
        got = runner.run_once(t, lambda b: jnp.mean(b, axis=0), combine="mean")
        np.testing.assert_allclose(np.asarray(got), X.mean(0), rtol=1e-5)


class TestPartitionApply:
    def test_concat_is_identity_for_identity_fn(self, rng):
        X = np.asarray(rng.normal(size=(24, 3)), np.float32)
        t = MLNumericTable.from_numpy(X, num_shards=4)
        runner = DistributedRunner.for_table(t)
        got = runner.partition_apply(t.data, lambda b: b * 1.0, combine="concat")
        np.testing.assert_allclose(np.asarray(got), X, rtol=1e-6)

    def test_stacked_shape(self, rng):
        X = np.asarray(rng.normal(size=(24, 3)), np.float32)
        t = MLNumericTable.from_numpy(X, num_shards=4)
        runner = DistributedRunner.for_table(t)
        stacked = runner.partition_apply(t.data, lambda b: jnp.sum(b, 0)[None])
        assert stacked.shape == (4, 1, 3)

    def test_broadcast_args(self, rng):
        X = np.asarray(rng.normal(size=(16, 4)), np.float32)
        t = MLNumericTable.from_numpy(X, num_shards=2)
        runner = DistributedRunner.for_table(t)
        w = jnp.ones((4,), jnp.float32)
        got = runner.partition_apply(t.data, lambda b, ww: b @ ww,
                                     broadcast=(w,), combine="concat")
        np.testing.assert_allclose(np.asarray(got), X.sum(1), rtol=1e-5)


class TestRunRounds:
    def test_full_batch_gd_matches_closed_loop(self, rng):
        """sum-combined gradient rounds == the same loop written by hand."""
        X = np.asarray(rng.normal(size=(32, 3)), np.float32)
        w_true = np.asarray(rng.normal(size=3), np.float32)
        y = X @ w_true
        data = np.concatenate([y[:, None], X], 1).astype(np.float32)
        t = MLNumericTable.from_numpy(data, num_shards=4)
        runner = DistributedRunner.for_table(t)
        lr = 0.01

        def local_grad(block, w, r):
            x, yy = block[:, 1:], block[:, 0]
            return jnp.sum(x * (x @ w - yy)[:, None], axis=0)

        got = runner.run_rounds(
            t, jnp.zeros(3, jnp.float32), local_grad, 20, combine="sum",
            update=lambda w, g, r: w - lr * g)

        w = np.zeros(3, np.float32)
        for _ in range(20):
            w = w - lr * (X.T @ (X @ w - y))
        np.testing.assert_allclose(np.asarray(got), w, rtol=1e-4, atol=1e-5)

    def test_shard_invariance(self, rng):
        """mean-combined rounds over equal partitions must not depend on the
        partition count when every partition computes the same statistic."""
        X = np.asarray(rng.normal(size=(32, 3)), np.float32)
        outs = []
        for shards in (1, 2, 8):
            t = MLNumericTable.from_numpy(X, num_shards=shards)
            runner = DistributedRunner.for_table(t)
            out = runner.run_rounds(
                t, jnp.zeros(3, jnp.float32),
                lambda b, s, r: s + jnp.mean(b, axis=0), 3, combine="mean")
            outs.append(np.asarray(out))
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], rtol=1e-4, atol=1e-4)

    def test_schedule_knob_accepts_strings(self, rng):
        X = np.asarray(rng.normal(size=(16, 2)), np.float32)
        t = MLNumericTable.from_numpy(X, num_shards=4)
        for sched in ("allreduce", "gather_broadcast", "reduce_scatter"):
            runner = DistributedRunner.for_table(t, schedule=sched)
            assert runner.schedule is CollectiveSchedule.parse(sched)


# --------------------------------------------------------------------------- #
# emulated partitions run one after another: each partition's local pass is
# the plain local_step on its own block, as on one chip of a mesh
# --------------------------------------------------------------------------- #
def _minibatch_sgd(block, w, r):
    """A local pass shaped like Fig. A4's: a scan over 4-row minibatches."""
    lr = 0.5 / (1.0 + r)
    chunks = block.reshape(-1, 4, block.shape[1])

    def step(w, chunk):
        x, y = chunk[:, 1:], chunk[:, 0]
        g = jnp.mean(x * (jax.nn.sigmoid(x @ w) - y)[:, None], axis=0)
        return w - lr * g, None

    return jax.lax.scan(step, w, chunks)[0]


def _gradient_stats(block, w, r):
    x, y = block[:, 1:], block[:, 0]
    return {"g": jnp.sum(x * (x @ w - y)[:, None], axis=0),
            "n": jnp.asarray(block.shape[0], jnp.float32)}


def _gradient_update(w, stats, r):
    return w - 0.1 / (1.0 + r) * stats["g"] / stats["n"]


_LOCAL_PASSES = {
    "mean": (_minibatch_sgd, None),
    "sum": (_gradient_stats, _gradient_update),
}


def _plain_rounds(blocks, w, local_step, update, combine, rounds):
    """The runner's contract written as a plain loop: each round applies
    local_step to every block, then combines and updates in NumPy."""
    for r in rounds:
        r = jnp.asarray(r, jnp.int32)
        parts = [jax.tree.map(np.asarray, local_step(b, w, r)) for b in blocks]
        if combine == "mean":
            w = np.mean(np.stack(parts), axis=0)
        else:
            summed = jax.tree.map(lambda *xs: np.sum(np.stack(xs), axis=0),
                                  *parts)
            w = np.asarray(update(w, summed, r))
    return w


def _assert_rel(got, want, tol=1e-6):
    err = np.abs(np.asarray(got) - want).max() / np.abs(want).max()
    assert err <= tol, err


def _block_pass(block, w):
    """A one-shot pass with a per-row and a per-column result, so that every
    combine (concat included) has something to join."""
    return {"margin": block[:, 1:] @ w, "colsum": jnp.sum(block, axis=0)}


# each combine written in NumPy over the (partitions, ...) stacked results
_PLAIN_COMBINES = {
    None: lambda x: x,
    "mean": lambda x: np.mean(x, axis=0),
    "sum": lambda x: np.sum(x, axis=0),
    "concat": lambda x: x.reshape((-1,) + x.shape[2:]),
}


@pytest.mark.parametrize("combine", ["mean", "sum", None, "concat"])
@pytest.mark.parametrize("num_shards", [1, 2, 4])
def test_emulated_partitions_match_plain_loop(rng, num_shards, combine):
    """partition_apply, run_rounds and epoch_fn on emulated partitions equal
    a plain loop over the blocks of partition_rows, and each launch of
    run_rounds counts one partition pass per partition per round.  The
    rounds take the combines of an iterative method, mean and sum."""
    from repro.core import trace

    X = np.asarray(rng.normal(size=(32, 4)), np.float32)
    y = (X @ np.asarray(rng.normal(size=4), np.float32) > 0).astype(np.float32)
    data = np.concatenate([y[:, None], X], 1)
    w0 = np.asarray(rng.normal(size=4) * 0.1, np.float32)
    blocks = list(np.asarray(pt.partition_rows(jnp.asarray(data), num_shards)))
    t = MLNumericTable.from_numpy(data, num_shards=num_shards)
    runner = DistributedRunner.for_table(t)

    got = runner.partition_apply(t.data, _block_pass, (jnp.asarray(w0),),
                                 combine=combine)
    parts = [jax.tree.map(np.asarray, _block_pass(b, w0)) for b in blocks]
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *parts)
    want = jax.tree.map(_PLAIN_COMBINES[combine], stacked)
    assert jax.tree.map(np.shape, got) == jax.tree.map(np.shape, want)
    jax.tree.map(_assert_rel, got, want)
    if combine not in _LOCAL_PASSES:
        return

    local_step, update = _LOCAL_PASSES[combine]
    before = trace.snapshot().get("runner.partition_passes", 0)
    got = runner.run_rounds(t, jnp.asarray(w0), local_step, 3,
                            combine=combine, update=update)
    after = trace.snapshot().get("runner.partition_passes", 0)
    assert after - before == num_shards * 3
    _assert_rel(got, _plain_rounds(blocks, w0, local_step, update, combine,
                                   range(3)))

    # two rounds per window: round r reads chunk r % 2 of every partition
    epoch = runner.epoch_fn(local_step, update, combine=combine,
                            chunks_per_epoch=2)
    rounds = np.arange(4, 6, dtype=np.int32)
    got = epoch(jnp.asarray(w0), jnp.asarray(data), jnp.asarray(rounds))
    w, half = w0, blocks[0].shape[0] // 2
    for r in rounds:
        chunks = [b[(r % 2) * half:(r % 2 + 1) * half] for b in blocks]
        w = _plain_rounds(chunks, w, local_step, update, combine, [r])
    _assert_rel(got, w)


# --------------------------------------------------------------------------- #
# streaming mode (emulated partitions; mesh + kill behavior is covered by
# tests/test_streaming_resume.py subprocesses)
# --------------------------------------------------------------------------- #
def _window_source(rows, cols, base_seed=7):
    def source(step):
        srng = np.random.default_rng(base_seed + step)
        return {"data": srng.normal(size=(rows, cols)).astype(np.float32)}
    return source


class TestRunEpochs:
    def test_constant_stream_matches_run_rounds(self, rng):
        """A stream that replays the resident table every epoch with one
        chunk per epoch is mathematically run_rounds — the streaming loop
        must reproduce it exactly."""
        X = np.asarray(rng.normal(size=(32, 3)), np.float32)
        t = MLNumericTable.from_numpy(X, num_shards=4)
        runner = DistributedRunner.for_table(t)

        def local_step(block, s, r):
            return s + jnp.mean(block, axis=0) / (1.0 + r)

        resident = runner.run_rounds(t, jnp.zeros(3), local_step, 5,
                                     combine="mean")
        stream = BatchIterator(lambda step: {"data": X})
        streamed = runner.run_epochs(stream, jnp.zeros(3), local_step, 5,
                                     combine="mean")
        np.testing.assert_array_equal(np.asarray(streamed),
                                      np.asarray(resident))
        assert stream.step == 5

    def test_chunks_split_the_window_in_order(self, rng):
        """With chunks_per_epoch=c, round r must see the window's (r%c)-th
        row chunk of every partition, in order: weight each round's
        contribution by its round index and compare to the same walk done
        in numpy."""
        X = np.asarray(rng.normal(size=(16, 2)), np.float32)
        runner = DistributedRunner(num_shards=2)
        stream = BatchIterator(lambda step: {"data": X})
        got = runner.run_epochs(
            stream, jnp.zeros(2),
            lambda b, s, r: s + (r + 1.0) * jnp.sum(b, axis=0), 1,
            combine="mean", chunks_per_epoch=4)
        # shards of 8 rows, chunks of 2 rows: round r sees rows
        # [shard*8 + 2r, shard*8 + 2r+2) of each shard
        shards = X.reshape(2, 8, 2)
        expect = np.zeros(2, np.float32)
        for r in range(4):
            chunk_sums = shards[:, 2 * r: 2 * r + 2].sum(axis=1)  # (2, 2)
            expect = expect + (r + 1.0) * chunk_sums.mean(axis=0)
        np.testing.assert_allclose(np.asarray(got), expect, rtol=1e-5)

    def test_checkpoint_cadence_and_metadata(self, tmp_ckpt_dir):
        from repro.checkpoint import latest_step, load_metadata
        from repro.core.runner import CheckpointPolicy

        runner = DistributedRunner(num_shards=2)
        stream = BatchIterator(_window_source(8, 2))
        runner.run_epochs(stream, jnp.zeros(2),
                          lambda b, s, r: s + jnp.mean(b, 0), 5,
                          combine="mean", chunks_per_epoch=2,
                          checkpoint=CheckpointPolicy(tmp_ckpt_dir,
                                                      every_epochs=2))
        # epochs 2, 4 on cadence plus the final state at 5
        assert latest_step(tmp_ckpt_dir) == 5
        meta = load_metadata(tmp_ckpt_dir, step=4)
        assert meta["epoch"] == 4 and meta["stream_step"] == 4
        assert meta["chunks_per_epoch"] == 2 and meta["num_shards"] == 2
        assert meta["schedule"] == "allreduce"

    def test_resume_rejects_mismatched_layout(self, tmp_ckpt_dir):
        from repro.core.runner import CheckpointPolicy

        step = lambda b, s, r: s + jnp.mean(b, 0)
        runner = DistributedRunner(num_shards=2)
        runner.run_epochs(BatchIterator(_window_source(8, 2)), jnp.zeros(2),
                          step, 2, checkpoint=CheckpointPolicy(tmp_ckpt_dir))
        with pytest.raises(ValueError, match="num_shards"):
            DistributedRunner(num_shards=4).resume(
                tmp_ckpt_dir, BatchIterator(_window_source(8, 2)),
                jnp.zeros(2), step, 4)
        with pytest.raises(ValueError, match="schedule"):
            DistributedRunner(num_shards=2, schedule="reduce_scatter").resume(
                tmp_ckpt_dir, BatchIterator(_window_source(8, 2)),
                jnp.zeros(2), step, 4)
        with pytest.raises(ValueError, match="chunks_per_epoch"):
            runner.resume(tmp_ckpt_dir, BatchIterator(_window_source(8, 2)),
                          jnp.zeros(2), step, 4, chunks_per_epoch=8)

    def test_resume_past_target_returns_snapshot(self, tmp_ckpt_dir):
        from repro.core.runner import CheckpointPolicy

        step = lambda b, s, r: s + jnp.mean(b, 0)
        runner = DistributedRunner(num_shards=2)
        final = runner.run_epochs(BatchIterator(_window_source(8, 2)),
                                  jnp.zeros(2), step, 3,
                                  checkpoint=CheckpointPolicy(tmp_ckpt_dir))
        again = runner.resume(tmp_ckpt_dir, BatchIterator(_window_source(8, 2)),
                              jnp.zeros(2), step, 3)
        np.testing.assert_array_equal(np.asarray(again), np.asarray(final))

    def test_apply_stream_forwards_chunk_mismatch_on_resume(self, tmp_ckpt_dir):
        """The high-level streaming APIs must surface the checkpoint's
        chunk-layout cross-check, not swallow the caller's value."""
        from repro.core.optimizer import MinibatchSGD, MinibatchSGDParameters
        from repro.core.runner import CheckpointPolicy

        p = MinibatchSGDParameters(
            w_init=jnp.zeros(2),
            grad=lambda vec, w: vec[1:] * (jnp.dot(vec[1:], w) - vec[0]))
        opt = MinibatchSGD(p)
        ck = CheckpointPolicy(tmp_ckpt_dir)
        opt.apply_stream(BatchIterator(_window_source(8, 3)), 2, num_shards=2,
                         chunks_per_epoch=2, checkpoint=ck)
        with pytest.raises(ValueError, match="chunks_per_epoch"):
            opt.apply_stream(BatchIterator(_window_source(8, 3)), 4,
                             num_shards=2, chunks_per_epoch=4, checkpoint=ck,
                             resume=True)
        # omitting the value inherits the checkpointed layout
        opt.apply_stream(BatchIterator(_window_source(8, 3)), 4, num_shards=2,
                         checkpoint=ck, resume=True)

    def test_rejects_bad_windows(self):
        runner = DistributedRunner(num_shards=4)
        step = lambda b, s, r: s
        with pytest.raises(ValueError, match="divide"):
            runner.run_epochs(BatchIterator(_window_source(10, 2)),
                              jnp.zeros(2), step, 1)
        with pytest.raises(ValueError, match="chunks_per_epoch"):
            runner.run_epochs(BatchIterator(_window_source(16, 2)),
                              jnp.zeros(2), step, 1, chunks_per_epoch=3)


# --------------------------------------------------------------------------- #
# partition layer round-trip (property)
# --------------------------------------------------------------------------- #
@settings(max_examples=25, deadline=None)
@given(rows=st.integers(1, 64), cols=st.integers(1, 8),
       shards=st.sampled_from([1, 2, 3, 4, 8]), seed=st.integers(0, 2**16))
def test_partition_roundtrip_property(rows, cols, shards, seed):
    """pad → partition → unpartition → trim recovers any array exactly."""
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(rows, cols)), jnp.float32)
    padded, n_pad = pt.pad_rows(X, shards)
    assert padded.shape[0] % shards == 0
    assert n_pad == (-rows) % shards
    blocks = pt.partition_rows(padded, shards)
    assert blocks.shape == (shards, padded.shape[0] // shards, cols)
    back = pt.unpartition_rows(blocks)[:rows]
    np.testing.assert_array_equal(np.asarray(back), np.asarray(X))


def test_partition_rejects_indivisible():
    with pytest.raises(ValueError):
        pt.partition_rows(jnp.zeros((10, 2)), 3)


def test_runner_matches_table_layout(rng):
    X = np.asarray(rng.normal(size=(16, 2)), np.float32)
    t = MLNumericTable.from_numpy(X, num_shards=4)
    runner = DistributedRunner.for_table(t)
    assert runner.mesh is None and runner.num_shards == 4

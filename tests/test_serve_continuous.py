"""Continuous-batching serving: scheduler admission/backfill, ragged
prefill, decode edge cases, and the prediction service.

The load-bearing invariant throughout: the continuous engine's greedy
token stream is IDENTICAL per request to the slot-at-a-time reference
(``ServeEngine._run_one``) — mixed prompt lengths, mid-decode backfill,
ring caches, and the per-request fallback for recurrent stacks included.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models.transformer import TransformerLM, init_model
from repro.serve import (ModelPredictor, PredictRequest, Request, ServeEngine,
                         SlotScheduler)

KEY = jax.random.PRNGKey(1)


def make_requests(cfg, lens, news, seed=42, eos_id=None):
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, size=n)
                    .astype(np.int32), max_new_tokens=m, eos_id=eos_id)
            for n, m in zip(lens, news)]


@pytest.fixture(scope="module")
def qwen_engine():
    cfg = get_smoke("qwen2-1.5b")                    # dense GQA
    params, _ = init_model(KEY, cfg)
    return ServeEngine(cfg, params, batch_size=3, max_seq=96)


@pytest.fixture(scope="module")
def gemma_engine():
    cfg = get_smoke("gemma3-1b")                     # sliding-window ring cache
    params, _ = init_model(KEY, cfg)
    return ServeEngine(cfg, params, batch_size=3, max_seq=96)


def reference(engine, reqs):
    return [engine._run_one(Request(prompt=r.prompt.copy(),
                                    max_new_tokens=r.max_new_tokens,
                                    eos_id=r.eos_id)) for r in reqs]


# --------------------------------------------------------------------------- #
# scheduler (host-side, no jax)
# --------------------------------------------------------------------------- #
def test_scheduler_fifo_admission_and_backfill():
    sched = SlotScheduler(2)
    reqs = [Request(prompt=np.zeros(4, np.int32)) for _ in range(4)]
    for r in reqs:
        sched.submit(r)
    admits = sched.admit(0.0)
    assert [r for _, r in admits] == reqs[:2]        # FIFO into both slots
    assert sched.backfills == 0                      # nothing was mid-decode
    assert sched.queued() == 2 and sched.busy == 2
    sched.retire(0, 1.0)
    admits = sched.admit(1.0)                        # slot 1 still decoding
    assert [s for s, _ in admits] == [0] and admits[0][1] is reqs[2]
    assert sched.backfills == 1                      # counted as backfill
    sched.retire(0, 2.0)
    sched.retire(1, 2.0)
    sched.admit(2.0)
    assert sched.busy == 1 and not sched.queued()
    sched.retire(0, 3.0)
    assert not sched.has_work()
    rep = sched.report()
    assert rep["retired"] == 4 and rep["queue_depth_max"] == 2


def test_scheduler_holds_future_arrivals():
    sched = SlotScheduler(2)
    early = Request(prompt=np.zeros(4, np.int32), arrival=0.0)
    late = Request(prompt=np.zeros(4, np.int32), arrival=5.0)
    sched.submit(late)
    sched.submit(early)
    admits = sched.admit(1.0)
    assert [r for _, r in admits] == [early]         # late not yet released
    assert sched.next_arrival() == 5.0
    sched.retire(0, 2.0)
    assert [r for _, r in sched.admit(6.0)] == [late]
    assert early.admitted_at == 1.0 and late.admitted_at == 6.0


def test_scheduler_burst_release_is_arrival_fifo():
    """Regression: a burst trace submitted out of arrival order used to be
    released in *submission* order, letting a later-arriving request jump
    the queue when one ``release(now)`` covered several arrivals.  Release
    order must be ``(arrival, submission seq)`` — and stable for equal
    arrivals."""
    sched = SlotScheduler(1)
    arrivals = [3.0, 1.0, 2.0, 1.0, 0.0]             # submitted out of order
    reqs = [Request(prompt=np.zeros(4, np.int32), arrival=a)
            for a in arrivals]
    for r in reqs:
        sched.submit(r)
    # one release covering the whole burst: strict arrival order, with the
    # two arrival=1.0 requests kept in submission order (seq 1 before 3)
    order = []
    now = 10.0
    while sched.queued() or sched.busy:
        for slot, r in sched.admit(now):
            order.append(r)
            sched.retire(slot, now)
        now += 1.0
    assert order == [reqs[4], reqs[1], reqs[3], reqs[2], reqs[0]]
    assert [r.seq for r in order] == [4, 1, 3, 2, 0]


def test_scheduler_incremental_release_matches_burst_release():
    """The same trace released in many small ``admit`` calls (clock moving
    past each arrival) must admit in the same global order as one big
    release — FIFO cannot depend on the polling cadence."""
    arrivals = [0.5, 2.5, 1.5, 2.5, 0.5, 3.5]

    def drain(step):
        sched = SlotScheduler(1)
        reqs = [Request(prompt=np.zeros(4, np.int32), arrival=a)
                for a in arrivals]
        for r in reqs:
            sched.submit(r)
        order, now = [], 0.0
        while sched.has_work():
            for slot, r in sched.admit(now):
                order.append(r.seq)
                sched.retire(slot, now)
            now += step
        return order

    assert drain(0.25) == drain(100.0)


def test_engine_rejects_future_arrivals_on_frozen_clock(qwen_engine):
    req = Request(prompt=np.zeros(4, np.int32), max_new_tokens=1, arrival=9.9)
    with pytest.raises(ValueError, match="advancing clock"):
        qwen_engine.run([req])


# --------------------------------------------------------------------------- #
# continuous decode parity (the tentpole invariant)
# --------------------------------------------------------------------------- #
def test_mixed_lengths_with_backfill_match_slot_at_a_time(qwen_engine):
    """5 mixed-length requests through 3 slots: admission waves, staggered
    retirement, and mid-decode backfill — token streams must equal the
    slot-at-a-time reference exactly."""
    cfg = qwen_engine.cfg
    reqs = make_requests(cfg, (5, 9, 12, 7, 14), (3, 8, 5, 6, 4))
    sched = SlotScheduler(qwen_engine.batch)
    served = qwen_engine.run(reqs, scheduler=sched)
    for got, want in zip(served, reference(qwen_engine, reqs)):
        assert got.done and got.out_tokens == want.out_tokens
    assert sched.backfills > 0                       # truly mid-decode
    assert sched.report()["retired"] == len(reqs)


def test_sliding_window_arch_parity(gemma_engine):
    """Ring caches + ragged right-padded prefill: pad columns must never
    leak into the window (drop-mode cache writes)."""
    cfg = gemma_engine.cfg
    reqs = make_requests(cfg, (6, 11, 15, 8), (5, 4, 6, 3))
    served = gemma_engine.run(reqs)
    for got, want in zip(served, reference(gemma_engine, reqs)):
        assert got.out_tokens == want.out_tokens


def test_recurrent_arch_per_request_fallback_parity():
    """RG-LRU state would absorb a pad tail, so such stacks prefill
    per-request into the shared cache — fused per-slot decode still runs
    and must match slot-at-a-time.  (SSD stacks take the ragged path: their
    state holds through a pad tail; ``tests/test_granite_hybrid.py``.)"""
    cfg = get_smoke("recurrentgemma-9b")
    params, _ = init_model(KEY, cfg)
    engine = ServeEngine(cfg, params, batch_size=2, max_seq=64)
    assert not engine.ragged_ok
    reqs = make_requests(cfg, (6, 11, 8), (4, 3, 5), seed=9)
    served = engine.run(reqs)
    for got, want in zip(served, reference(engine, reqs)):
        assert got.out_tokens == want.out_tokens


def test_prefill_ragged_rejects_recurrent_stacks():
    cfg = get_smoke("recurrentgemma-9b")
    params, _ = init_model(KEY, cfg)
    model = TransformerLM(cfg)
    cache = model.init_cache(2, 32)
    toks = jnp.zeros((2, 8), jnp.int32)
    with pytest.raises(ValueError, match="RG-LRU"):
        model.prefill_ragged(params, toks, jnp.asarray([4, 8]), cache)


def test_ragged_prefill_logits_match_batch1(qwen_engine):
    """Model-level check under the engine tests: per-slot last-token logits
    of one right-padded ragged prefill equal each prompt's own batch-1
    prefill."""
    cfg, model, params = qwen_engine.cfg, qwen_engine.model, qwen_engine.params
    rng = np.random.default_rng(3)
    lens = [5, 9, 12]
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in lens]
    padded = np.zeros((3, max(lens)), np.int32)
    for i, p in enumerate(prompts):
        padded[i, : len(p)] = p
    cache = model.init_cache(3, 64)
    ragged, _ = model.prefill_ragged(params, jnp.asarray(padded),
                                     jnp.asarray(lens), cache)
    for i, p in enumerate(prompts):
        one, _ = model.prefill(params, jnp.asarray(p)[None, :],
                               model.init_cache(1, 64))
        np.testing.assert_allclose(
            np.asarray(ragged[i, 0], np.float32),
            np.asarray(one[0, -1], np.float32), rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# decode edge cases
# --------------------------------------------------------------------------- #
def test_eos_on_first_generated_token(qwen_engine):
    cfg = qwen_engine.cfg
    probe = make_requests(cfg, (10,), (1,), seed=5)
    first = reference(qwen_engine, probe)[0].out_tokens[0]
    reqs = make_requests(cfg, (10, 13), (6, 6), seed=5, eos_id=int(first))
    served = qwen_engine.run(reqs)
    assert served[0].out_tokens[0] == first and served[0].done
    assert served[0].out_tokens == reference(qwen_engine, reqs)[0].out_tokens
    assert served[1].out_tokens == reference(qwen_engine, reqs)[1].out_tokens


def test_max_new_tokens_zero_and_one(qwen_engine):
    cfg = qwen_engine.cfg
    reqs = make_requests(cfg, (8, 12, 9), (0, 1, 4))
    served = qwen_engine.run(reqs)
    assert served[0].out_tokens == [] and served[0].done
    refs = reference(qwen_engine, reqs)
    assert [len(r.out_tokens) for r in served] == [0, 1, 4]
    for got, want in zip(served, refs):
        assert got.out_tokens == want.out_tokens


def test_prompt_overflow_raises(qwen_engine):
    reqs = make_requests(qwen_engine.cfg, (90,), (10,))  # 90 + 10 > max_seq 96
    with pytest.raises(ValueError, match="max_seq"):
        qwen_engine.run(reqs)


def test_static_reference_still_groups_equal_lengths(qwen_engine):
    """run_static keeps the pre-refactor baseline semantics (used by
    benchmarks/serving_throughput.py) and matches the reference too."""
    cfg = qwen_engine.cfg
    reqs = make_requests(cfg, (8, 16, 8, 16, 24), (4, 4, 4, 4, 4))
    served = qwen_engine.run_static(reqs)
    for got, want in zip(served, reference(qwen_engine, reqs)):
        assert got.done and got.out_tokens == want.out_tokens


# --------------------------------------------------------------------------- #
# mesh placement (slot sharding; trivial 1-device mesh in tier-1, the
# 8-device version runs in the slow suite below)
# --------------------------------------------------------------------------- #
def test_engine_under_serving_mesh_smoke():
    from repro.launch.mesh import host_serving_setup

    cfg = get_smoke("qwen2-1.5b")
    params, axes = init_model(KEY, cfg)
    mesh, rules = host_serving_setup(cfg)
    engine = ServeEngine(cfg, params, batch_size=2, max_seq=64,
                         mesh=mesh, rules=rules, param_axes=axes)
    reqs = make_requests(cfg, (6, 9), (3, 3))
    served = engine.run(reqs)
    for got, want in zip(served, reference(engine, reqs)):
        assert got.out_tokens == want.out_tokens


@pytest.mark.slow
def test_slot_sharding_on_eight_devices(eight_device_run):
    """The shared cache's slot axis shards over an 8-device data axis and
    the served tokens still match the unsharded engine."""
    program = """
import json
import jax, numpy as np
from repro.configs import get_smoke
from repro.models.transformer import init_model
from repro.launch.mesh import host_serving_setup
from repro.serve import Request, ServeEngine

cfg = get_smoke("qwen2-1.5b")
params, axes = init_model(jax.random.PRNGKey(1), cfg)
mesh, rules = host_serving_setup(cfg)
assert mesh.devices.size == 8

def make():
    rng = np.random.default_rng(4)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, size=n).astype(np.int32),
                    max_new_tokens=m)
            for n, m in zip((5, 9, 12, 7, 14, 6, 10, 8, 11, 13),
                            (3, 6, 4, 5, 3, 6, 4, 5, 3, 4))]

sharded = ServeEngine(cfg, params, batch_size=8, max_seq=64,
                      mesh=mesh, rules=rules, param_axes=axes)
plain = ServeEngine(cfg, params, batch_size=8, max_seq=64)
a = sharded.run(make())
b = plain.run(make())
match = all(x.out_tokens == y.out_tokens for x, y in zip(a, b))
print("RESULT::" + json.dumps({"match": match,
                               "toks": [x.out_tokens for x in a]}))
"""
    res = eight_device_run(program)
    assert res["match"]


# --------------------------------------------------------------------------- #
# prediction service (classic-ML side of the stack)
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def kmeans_model():
    from repro.core.algorithms.kmeans import KMeans, KMeansParameters
    from repro.core.numeric_table import MLNumericTable

    rng = np.random.default_rng(1)
    X = (rng.normal(size=(64, 8)) + 4.0 * rng.integers(0, 3, size=(64, 1))
         ).astype(np.float32)
    table = MLNumericTable.from_numpy(X, num_shards=4)
    model = KMeans.train(table, KMeansParameters(k=3, max_iter=4))
    return model, X


def test_predictor_microbatches_split_and_rejoin(kmeans_model):
    model, X = kmeans_model
    service = ModelPredictor(model, max_batch=16)
    blocks = [X[:10], X[10:11], X[11:40], X[40:]]    # spans + tiny + short tail
    outs = service.predict_many(blocks)
    direct = np.asarray(model.predict(jnp.asarray(X)))
    np.testing.assert_array_equal(np.concatenate(outs), direct)
    rep = service.report()
    assert rep["batches"] == 4 and rep["rows_served"] == 64
    assert rep["rows_padded"] == 0                   # 64 rows = 4 full batches


def test_predictor_pads_short_final_batch(kmeans_model):
    model, X = kmeans_model
    service = ModelPredictor(model, max_batch=24)
    outs = service.predict_many([X[:50]])            # 50 = 24 + 24 + 2(+22 pad)
    np.testing.assert_array_equal(
        outs[0], np.asarray(model.predict(jnp.asarray(X[:50]))))
    assert service.report()["rows_padded"] == 22


def test_predictor_shard_aware_path(kmeans_model):
    model, X = kmeans_model
    sharded = ModelPredictor(model, max_batch=16, num_shards=4)
    plain = ModelPredictor(model, max_batch=16)
    a = sharded.predict_many([X[:16], X[16:48]])
    b = plain.predict_many([X[:16], X[16:48]])
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    with pytest.raises(ValueError, match="divide"):
        ModelPredictor(model, max_batch=10, num_shards=4)


def test_predictor_serves_supervised_model():
    from repro.core.algorithms.logistic_regression import (
        LogisticRegressionAlgorithm, LogisticRegressionParameters)
    from repro.core.numeric_table import MLNumericTable

    rng = np.random.default_rng(2)
    w = np.linspace(-1, 1, 6).astype(np.float32)
    X = rng.normal(size=(48, 6)).astype(np.float32)
    y = (X @ w > 0).astype(np.float32)
    table = MLNumericTable.from_numpy(np.concatenate([y[:, None], X], 1),
                                      num_shards=4)
    model = LogisticRegressionAlgorithm.train(
        table, LogisticRegressionParameters(max_iter=5))
    service = ModelPredictor(model, max_batch=16, num_shards=4)
    outs = service.predict_many([X[:5], X[5:31], X[31:]])
    np.testing.assert_array_equal(
        np.concatenate(outs), np.asarray(model.predict(jnp.asarray(X))))


def test_predictions_helper_concatenates_in_row_order(kmeans_model):
    from repro.core.numeric_table import MLNumericTable
    from repro.eval.metrics import predictions

    model, X = kmeans_model
    table = MLNumericTable.from_numpy(X, num_shards=4)
    got = predictions(table, model.predict)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(model.predict(jnp.asarray(X))))

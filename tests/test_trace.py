"""The program's instrumentation (repro.core.trace): host spans inside
``fit()`` and the runner, the counters the runner and JAX's events advance,
the one listener, and the names the programs carry into a profile."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import watch_compiles
from repro.core import trace
from repro.core.algorithms.logistic_regression import (
    LogisticRegressionAlgorithm)
from repro.core.numeric_table import MLNumericTable
from repro.core.runner import DistributedRunner

MAX_ITER = 3


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(0)
    data = rng.normal(size=(64, 9)).astype(np.float32)
    data[:, 0] = data[:, 1] > 0
    return MLNumericTable(jnp.asarray(data), num_shards=4)


@pytest.fixture(scope="module")
def algo(table):
    algo = LogisticRegressionAlgorithm(max_iter=MAX_ITER, local_batch_size=4)
    jax.block_until_ready(algo.fit(table).weights)       # warm
    return algo


def _delta(before, after):
    return {k: after[k] - before.get(k, 0) for k in after}


def _host_spans(trace_dir):
    """(thread line, start_ns, end_ns, name) of every ``mli.`` event."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((f"{plane.name}/{line.name}", ev.start_ns,
                        ev.start_ns + ev.duration_ns, ev.name)
                       for ev in line.events if ev.name.startswith("mli."))
    return out


def test_fit_records_nested_spans_on_one_thread(algo, table, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(algo.fit(table).weights)
    spans = {name: (line, s, e) for line, s, e, name in _host_spans(tmp_path)}
    assert set(spans) == {"mli.fit", "mli.runner.prepare", "mli.runner.launch"}
    assert len({line for line, _, _ in spans.values()}) == 1
    _, fs, fe = spans["mli.fit"]
    _, ps, pe = spans["mli.runner.prepare"]
    _, ls, le = spans["mli.runner.launch"]
    assert fs <= ps <= pe <= ls <= le <= fe


def test_counters_advance_by_one_launch_per_fit(algo, table):
    before = trace.snapshot()
    jax.block_until_ready(algo.fit(table).weights)
    d = _delta(before, trace.snapshot())
    assert d["runner.launches"] == 1
    assert d["runner.rounds"] == MAX_ITER
    assert d["runner.launch_s"] > 0


def test_a_second_fit_traces_and_builds_its_program_again(algo, table):
    """``rounds_fn`` makes a new jit every fit: a warmed estimator still
    traces, and builds one program (a compile here, a persistent-cache load
    where the cache is on)."""
    before = trace.snapshot()
    jax.block_until_ready(algo.fit(table).weights)
    d = _delta(before, trace.snapshot())
    assert d.get("jax.builds", 0) >= 1
    assert d.get("jax.traces", 0) >= 1
    assert d.get("jax.build_s", 0) > 0 and d.get("jax.trace_s", 0) > 0


def test_staged_run_rounds_opens_no_span_and_counts_no_launch(table,
                                                              tmp_path):
    runner = DistributedRunner(num_shards=4)
    step = lambda block, w, r: w + jnp.mean(block[:, 1:], axis=0)
    before = trace.snapshot()
    with jax.profiler.trace(str(tmp_path)):
        jax.make_jaxpr(lambda: runner.run_rounds(
            table, jnp.zeros(8), step, 2))()
    d = _delta(before, trace.snapshot())
    assert d.get("runner.launches", 0) == 0
    assert _host_spans(tmp_path) == []


def test_the_listeners_are_registered_once(algo, table):
    from jax._src import monitoring

    for _ in range(3):
        with watch_compiles():
            algo.fit(table)
    assert monitoring.get_event_duration_listeners().count(
        trace._on_duration) == 1
    assert monitoring.get_event_listeners().count(trace._on_event) == 1


def test_a_persistent_cache_load_counts_as_a_build(tmp_path):
    """A fresh jit of a program already in the persistent cache loads it
    from there: the retrace sentinel counts that as a build, as it counts a
    compile."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}

    def fresh():
        def scaled(x):
            return x * 3.0 + 1.0
        return jax.jit(scaled)

    x = jnp.arange(11.0)
    try:
        cc.reset_cache()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.block_until_ready(fresh()(x))              # compiled, written
        before = trace.snapshot()
        with watch_compiles() as w:
            jax.block_until_ready(fresh()(x))
        d = _delta(before, trace.snapshot())
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert w.compiles == 1
    assert d.get("jax.builds") == 1
    assert d.get("jax.cache_loads") == 1


def test_serving_programs_carry_their_names():
    from repro.configs import get_smoke
    from repro.models.transformer import init_model
    from repro.serve.engine import ServeEngine
    from repro.serve.prefix_cache import RadixPrefixCache

    cfg = get_smoke("qwen2-1.5b")
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, batch_size=2, max_seq=32,
                         prefix_cache=RadixPrefixCache(block_size=4,
                                                       capacity_blocks=8))
    names = {f.__name__ for f in (engine._prefill, engine._prefill_ragged,
                                  engine._prefill_ragged_start,
                                  engine._decode, engine._scatter)}
    assert names == {"serve_prefill", "serve_prefill_ragged",
                     "serve_prefill_ragged_start", "serve_decode",
                     "serve_scatter"}


def test_the_rounds_program_is_named_mli_rounds(table):
    runner = DistributedRunner(num_shards=4)
    run = runner.rounds_fn(lambda block, w, r: w, 2)
    text = run.lower(jnp.zeros(8), table.data).as_text()
    assert "jit_mli_rounds" in text

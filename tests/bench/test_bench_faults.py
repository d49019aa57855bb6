"""Each cell's correctness check, driven through the harness at a size a
test can hold, with the harness's look for a chip skipped: a sound run
comes out correct, and each fault the cell can have, planted in the timed
path underneath, and each cell's control come out not correct.  The limits
are the cells' own, from their configuration files."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import harness  # noqa: E402
from bench.drivers import fit  # noqa: E402
from bench.reference.fig_a4 import fig_a4  # noqa: E402

LOGREG = {"rows_per_chip": 256, "features": 48, "minibatch": 8}
# At this width the attention of the cell's own weights is too flat for a
# dropped cache update to change the served tokens, so q, k and v are
# sharper here; outputs are long enough for the fault to act.
QWEN = {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 4096, "serving": {"slots": 4, "max_seq": 128},
        "weights": {"std": {"default": 0.02, "wq": 0.1, "wk": 0.1,
                            "wv": 0.1}}}
DECODE_HEAVY = {"rate_per_s": 6.0, "check_requests": 3,
                "prompt_tokens": {"median": 12, "sigma": 0.5, "min": 4,
                                  "max": 30},
                "output_tokens": {"median": 64, "sigma": 0.3, "min": 48,
                                  "max": 96},
                "trace_slice_s": [0.5, 1.0]}
SERVE_SEED = 5


def small_root(tmp_path) -> pathlib.Path:
    """The repository's benchmark tree with its configurations and mixes cut
    to a test's size; drivers, readers, limits and peaks as they are (the
    peaks also under ``cpu``)."""
    bench = tmp_path / "bench"
    shutil.copytree(REPO / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    # every cell that has a file, listed or not in BENCHMARK.json
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = {w["name"] for w in spec["workloads"]}
    configs = {c["name"] for c in spec["configs"]}
    for path in sorted((bench / "workloads").glob("*.json")):
        cell = json.loads(path.read_text())
        if path.stem not in listed:
            spec["workloads"].append({"name": path.stem, "why": "test",
                                      **{k: cell[k] for k in
                                         ("config", "traffic", "chips")}})
        if cell["config"] not in configs:
            configs.add(cell["config"])
            spec["configs"].append({
                "name": cell["config"],
                "file": f"bench/configs/{cell['config']}.json"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["cpu"] = next(iter(peaks.values()))
    (bench / "peaks.json").write_text(json.dumps(peaks))

    def cut(path, changes):
        data = json.loads(path.read_text())
        data.update(changes)
        path.write_text(json.dumps(data))

    cut(bench / "configs" / "logreg-160k.json", LOGREG)
    cut(bench / "configs" / "qwen2-1.5b.json", QWEN)
    cut(bench / "traffic" / "decode-heavy.json", DECODE_HEAVY)
    return tmp_path


def run(root, cell, seconds=1.0, seed=2**31 + 99):
    return harness.run_cell(harness.find_cell(cell, root), seed=seed,
                            seconds=seconds, trace=False,
                            t_start=time.perf_counter(), require_tpu=False,
                            root=root)


# --------------------------------------------------------------------------- #
# logreg-160k.resident
# --------------------------------------------------------------------------- #
def test_logreg_sound_run_is_correct(tmp_path):
    out = run(small_root(tmp_path), "logreg-160k.resident")
    assert out["correct"] is True, out["checks"]


def test_logreg_state_returned_unchanged_is_caught(tmp_path, monkeypatch):
    from repro.core.runner import DistributedRunner

    monkeypatch.setattr(DistributedRunner, "run_rounds",
                        lambda self, table, init_state, *a, **k: init_state)
    assert run(small_root(tmp_path), "logreg-160k.resident")["correct"] is False


def test_logreg_half_of_each_minibatch_left_out_is_caught(tmp_path,
                                                          monkeypatch):
    from repro.core import optimizer

    whole = optimizer.StochasticGradientDescent._local_round

    def half(p):
        bs = int(p.local_batch_size)
        local = whole(type(p)(**{**p.__dict__, "local_batch_size": bs // 2}))

        def step(block, w, r):
            kept = block.reshape(-1, bs, block.shape[1])[:, : bs // 2]
            return local(kept.reshape(-1, block.shape[1]), w, r)
        return step

    monkeypatch.setattr(optimizer.StochasticGradientDescent, "_local_round",
                        staticmethod(half))
    assert run(small_root(tmp_path), "logreg-160k.resident")["correct"] is False


def test_logreg_answer_altered_where_produced_is_caught(tmp_path,
                                                        monkeypatch):
    from repro.core.algorithms import logistic_regression as lr_mod

    sound = lr_mod.LogisticRegressionAlgorithm.fit

    def altered(self, table):
        model = sound(self, table)
        w = model.weights
        model.weights = w.at[0].add(0.05 * jnp.max(jnp.abs(w)))
        return model

    monkeypatch.setattr(lr_mod.LogisticRegressionAlgorithm, "fit", altered)
    assert run(small_root(tmp_path), "logreg-160k.resident")["correct"] is False


def test_logreg_bfloat16_control_fails_the_limit(tmp_path):
    root = small_root(tmp_path)
    cell = harness.find_cell("logreg-160k.resident", root)
    limit = cell.config["limits"]["weights_rel_err"]
    for seed in (1, 2, 3):
        data = fit.make_table(seed, 256, 48, None)
        blocks = [(data, p * 64, 64) for p in range(4)]
        lr = cell.config["lr_times_features"] / 48
        args = (blocks, lr, cell.config["max_iter"], 8)
        w_ref = fig_a4(*args)
        assert fit.rel_err(fig_a4(*args, "bfloat16"), w_ref) > limit


# --------------------------------------------------------------------------- #
# logreg-160k.4chip: the exchange between chips left out
# --------------------------------------------------------------------------- #
FOUR_CHIPS = textwrap.dedent("""
    import json, pathlib, sys, time
    sys.path[:0] = [{repo!r}, {src!r}, {here!r}]
    import test_bench_faults as t
    from bench import harness
    root = pathlib.Path({root!r})
    if {broken}:
        from repro.core import runner
        runner._COMBINERS["mean"] = lambda part, axes, schedule: part
    out = harness.run_cell(harness.find_cell("logreg-160k.4chip", root),
                           seed=5, seconds=0.5, trace=False,
                           t_start=time.perf_counter(), require_tpu=False,
                           root=root)
    print("RESULT " + json.dumps(out["correct"]))
""")


@pytest.mark.parametrize("broken", [False, True],
                         ids=["sound", "exchange_left_out"])
def test_four_chip_exchange_left_out_is_caught(tmp_path, broken):
    root = small_root(tmp_path)
    program = FOUR_CHIPS.format(repo=str(REPO), src=str(REPO / "src"),
                                here=str(REPO / "tests" / "bench"),
                                root=str(root), broken=broken)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", program], env=env,
                         capture_output=True, text=True, timeout=300)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    assert lines, out.stderr[-3000:]
    assert json.loads(lines[-1][7:]) is (not broken)


# --------------------------------------------------------------------------- #
# qwen2-1.5b.decode-heavy
# --------------------------------------------------------------------------- #
def _wrap_decode(monkeypatch, change):
    from repro.serve import engine as engine_mod

    init = engine_mod.ServeEngine.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        decode = self._decode
        self._decode = lambda p, t, pos, c: change(decode(p, t, pos, c), c)

    monkeypatch.setattr(engine_mod.ServeEngine, "__init__", patched)


def test_serving_sound_run_is_correct(tmp_path):
    out = run(small_root(tmp_path), "qwen2-1.5b.decode-heavy", seconds=2.0,
              seed=SERVE_SEED)
    assert out["correct"] is True, out["checks"]


def test_serving_cell_reports_the_metrics_benchmark_json_lists(tmp_path):
    """The result line holds exactly the cell's end-to-end metrics, and each
    per-layer reader the cell lists reads a finite value from the driver's
    own slice counts beside a hand-built trace summary."""
    import math
    import types

    from bench.drivers import serve
    from bench.traffic import serving_requests

    name = "qwen2-1.5b.decode-heavy"
    root = small_root(tmp_path)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    out = run(root, name, seconds=2.0, seed=SERVE_SEED)
    assert set(out["metrics"]) == {
        m["name"] for m in spec["end_to_end"]
        if name in m.get("workloads", [name])}
    assert all(math.isfinite(m["value"]) for m in out["metrics"].values())

    cell = harness.find_cell(name, root)
    cfg = cell.config
    engine, params = serve.build_engine(cfg, SERVE_SEED)
    plan = serving_requests(cell.traffic, 1.0, SERVE_SEED, cfg["vocab_size"])
    requests, start = serve.serve_window(engine, plan, lambda now: None,
                                         time.perf_counter)
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    last = max(r.out_tokens.times[-1] for r in requests)
    stats = {"slots": engine.batch,
             **serve._slice_stats(cfg, requests, start, weight_bytes, start,
                                  last)}
    turns = stats["decode_steps"]
    trace = {"module_s": {"jit_serve_decode(1)": 0.5,
                          "jit_serve_prefill_ragged(2)": 0.2},
             "module_n": {"jit_serve_decode(1)": turns,
                          "jit_serve_prefill_ragged(2)": turns},
             "busy_s": 0.8, "window_s": 1.0}
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    view = types.SimpleNamespace(trace=trace, stats=stats, e2e={}, config=cfg,
                                 traffic=cell.traffic, chips=1,
                                 peaks=peaks["TPU v5 lite"])
    readers = [m["name"] for m in spec["per_layer"]
               if name in m.get("workloads", [name])]
    assert readers and all(r.startswith("serve.") for r in readers)
    for reader in readers:
        value = harness.load_metric(reader, root).read(view)
        assert value is not None and math.isfinite(value), reader


def test_serving_token_altered_where_produced_is_caught(tmp_path,
                                                        monkeypatch):
    _wrap_decode(monkeypatch,
                 lambda out, cache: (jnp.roll(out[0], 1, axis=-1), out[1]))
    out = run(small_root(tmp_path), "qwen2-1.5b.decode-heavy", seconds=2.0,
              seed=SERVE_SEED)
    assert out["correct"] is False


def test_serving_cache_returned_unchanged_is_caught(tmp_path, monkeypatch):
    _wrap_decode(monkeypatch, lambda out, cache: (out[0], cache))
    out = run(small_root(tmp_path), "qwen2-1.5b.decode-heavy", seconds=2.0,
              seed=SERVE_SEED)
    assert out["correct"] is False


def test_serving_half_of_the_slots_left_out_is_caught(tmp_path, monkeypatch):
    """The decode step computes every other slot only; each odd slot is
    handed the token of the even slot before it.  The scheduler fills the
    lowest free slot first, so odd and even slots serve about as many
    requests.  Here the check reads every request, so that which slots the
    sampled ones held, which the host's timing decides, cannot hide it."""
    def half(out, cache):
        logits = out[0]
        return logits.at[1::2].set(logits[0::2]), out[1]

    root = small_root(tmp_path)
    mix = root / "bench" / "traffic" / "decode-heavy.json"
    mix.write_text(json.dumps(dict(json.loads(mix.read_text()),
                                   check_requests=10**6)))
    _wrap_decode(monkeypatch, half)
    out = run(root, "qwen2-1.5b.decode-heavy", seconds=2.0, seed=SERVE_SEED)
    assert out["correct"] is False


def test_serving_int8_control_reads_far_above_the_program(tmp_path):
    """The control (the program's int8 path, teacher-forced) at this size.
    Its gaps are smaller here than at the cell's widths, where they are
    read against the limit (``bench/calibrate.py``; PERF.md gives the
    readings), so here it has to read three times the sound program's
    widest gap on the same seeds."""
    from bench import calibrate
    from bench.drivers import serve
    from bench.traffic import serving_requests

    cell = harness.find_cell("qwen2-1.5b.decode-heavy", small_root(tmp_path))
    cfg, mix = cell.config, cell.traffic
    sound, control = [], []
    for seed in (1, 2, 3):
        engine, params = serve.build_engine(cfg, seed)
        plan = serving_requests(mix, 1.0, seed, cfg["vocab_size"])
        requests, _ = serve.serve_window(engine, plan, lambda now: None,
                                         time.perf_counter)
        check = serve.sample_for_check(requests, seed, mix["check_requests"])
        sound.append(serve.reference_gaps(cfg, params, check).max())
        picks = calibrate.control_tokens(cfg, params, check)
        control.append(serve.reference_gaps(cfg, params, check,
                                            chosen=picks).max())
    assert min(control) > 0 and max(control) > 3 * max(sound)

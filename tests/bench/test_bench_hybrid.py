"""The granite-4.0-h-micro.chat cell's correctness check, driven through the
harness at a size a test can hold, with the harness's look for a chip
skipped: a sound run comes out correct, each fault the hybrid serving path
can have, planted underneath, comes out not correct, and the
lower-precision control reads far above the program.  The limit is the
cell's own, from its configuration file."""
import json
import math
import time
import types

import jax
import jax.numpy as jnp
import pytest

from test_bench_faults import REPO, run, small_root

from bench import harness  # noqa: E402

CELL = "granite-4.0-h-micro.chat"
# one whole period of the layer pattern at tiny widths.  At the cell's
# stds the branches, times 0.22, are small beside the embeddings, times 12,
# at this width, and the served token is the tied embedding's echo of the
# last one whatever the state holds; so the branches' weights are wider, the
# embeddings narrower, and q and k wider still for the 1/16-scaled attention
HYBRID = {"hidden_size": 64, "intermediate_size": 128,
          "shared_intermediate_size": 128, "num_hidden_layers": 10,
          "layer_types": ["mamba"] * 5 + ["attention"] + ["mamba"] * 4,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "attention_multiplier": 1 / 16, "mamba_n_heads": 8,
          "mamba_d_head": 16, "mamba_d_state": 16, "mamba_chunk_size": 16,
          "vocab_size": 2048,
          "serving": {"slots": 4, "max_seq": 128, "pad_to": 16},
          "weights": {"std": {"default": 0.2, "tok": 0.02, "wq": 0.3,
                              "wk": 0.3, "conv_w": 0.29, "conv_b": 0.29}}}
CHAT = {"rate_per_s": 6.0, "check_requests": 3,
        "prompt_tokens": {"median": 14, "sigma": 0.5, "min": 5, "max": 30},
        "output_tokens": {"median": 40, "sigma": 0.3, "min": 32, "max": 64},
        "trace_slice_s": [0.5, 1.0]}
SEED = 7


def hybrid_root(tmp_path):
    root = small_root(tmp_path)
    for path, changes in ((root / "bench" / "configs"
                           / "granite-4.0-h-micro.json", HYBRID),
                          (root / "bench" / "traffic" / "chat.json", CHAT)):
        path.write_text(json.dumps(dict(json.loads(path.read_text()),
                                        **changes)))
    return root


def serve(tmp_path, every_request=False):
    root = hybrid_root(tmp_path)
    if every_request:
        mix = root / "bench" / "traffic" / "chat.json"
        mix.write_text(json.dumps(dict(json.loads(mix.read_text()),
                                       check_requests=10**6)))
    return run(root, CELL, seconds=2.0, seed=SEED)


def _wrap_decode(monkeypatch, change):
    from repro.serve import engine as engine_mod

    init = engine_mod.ServeEngine.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        decode = self._decode
        self._decode = lambda p, t, pos, c: change(decode(p, t, pos, c), c)

    monkeypatch.setattr(engine_mod.ServeEngine, "__init__", patched)


def _is_ssd(path) -> bool:
    return any(getattr(k, "key", None) == "ssd" for k in path)


def test_hybrid_sound_run_is_correct(tmp_path):
    out = serve(tmp_path)
    assert out["correct"] is True, out["checks"]


def test_hybrid_cell_reports_the_metrics_benchmark_json_lists(tmp_path):
    """The result line holds exactly the cell's end-to-end metrics; each
    per-layer reader the cell lists reads a finite value from the driver's
    own counts beside a hand-built trace summary."""
    from bench.drivers import serve_hybrid
    from bench.traffic import serving_requests
    from repro.core import trace

    root = hybrid_root(tmp_path)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    out = run(root, CELL, seconds=2.0, seed=SEED)
    assert set(out["metrics"]) == {
        m["name"] for m in spec["end_to_end"]
        if CELL in m.get("workloads", [CELL])}
    assert all(math.isfinite(m["value"]) for m in out["metrics"].values())

    cell = harness.find_cell(CELL, root)
    cfg = cell.config
    engine, params = serve_hybrid.build_engine(cfg, SEED)
    plan = serving_requests(cell.traffic, 1.0, SEED, cfg["vocab_size"])
    before = trace.snapshot()
    requests, start = serve_hybrid.serve_window(engine, plan, lambda now: None,
                                                time.perf_counter)
    after = trace.snapshot()
    last = max(r.out_tokens.times[-1] for r in requests)
    stats = {"slots": engine.batch, "admit_ms": [3.0, 5.0],
             "prefill_tokens": after["serve.prefill_tokens"]
             - before.get("serve.prefill_tokens", 0),
             "prefill_pad_tokens": after["serve.prefill_pad_tokens"]
             - before.get("serve.prefill_pad_tokens", 0),
             "weight_bytes": sum(x.nbytes for x in jax.tree.leaves(params)),
             "state_bytes": serve_hybrid.state_bytes(engine.cfg, engine.batch),
             **serve_hybrid._slice_stats(engine.cfg, requests, start, start,
                                         last)}
    assert 0 < stats["prefill_pad_tokens"] < stats["prefill_tokens"]
    turns = stats["decode_steps"]
    summary = {"module_s": {"jit_serve_decode(1)": 0.5,
                            "jit_serve_prefill_ragged(2)": 0.2},
               "module_n": {"jit_serve_decode(1)": turns,
                            "jit_serve_prefill_ragged(2)": turns},
               "busy_s": 0.8, "window_s": 1.0}
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    view = types.SimpleNamespace(trace=summary, stats=stats, e2e={},
                                 config=cfg, traffic=cell.traffic, chips=1,
                                 peaks=peaks["TPU v5 lite"])
    readers = [m["name"] for m in spec["per_layer"]
               if CELL in m.get("workloads", [CELL])]
    assert {r for r in readers if r.startswith("hybrid.")} == {
        "hybrid.decode_hbm_roofline", "hybrid.step_mfu",
        "hybrid.admit_ms_p95", "hybrid.prefill_pad_share"}
    for reader in readers:
        value = harness.load_metric(reader, root).read(view)
        assert value is not None and math.isfinite(value), reader


def test_hybrid_pad_tail_advancing_the_state_is_caught(tmp_path, monkeypatch):
    """The SSD blocks run the right-padded prefill as if every column were
    real: Δ at pad columns advances ``h``, and the conv taps are the pad
    tail's."""
    from repro.models import transformer

    sound = transformer.ssd_apply
    monkeypatch.setattr(transformer, "ssd_apply",
                        lambda *a, lengths=None, **k: sound(*a, **k))
    assert serve(tmp_path)["correct"] is False


def test_hybrid_reused_slot_not_reset_is_caught(tmp_path, monkeypatch):
    """An admitted request's recurrent state is added to what the slot's
    last request left, instead of replacing it."""
    from repro.serve import engine as engine_mod

    def stale(cache, sub, slots):
        return jax.tree_util.tree_map_with_path(
            lambda path, full, new: (full.at[:, slots].add(new, mode="drop")
                                     if _is_ssd(path) else
                                     full.at[:, slots].set(new, mode="drop")),
            cache, sub)

    monkeypatch.setattr(engine_mod, "serve_scatter", stale)
    assert serve(tmp_path, every_request=True)["correct"] is False


def test_hybrid_decode_state_returned_unchanged_is_caught(tmp_path,
                                                          monkeypatch):
    """The decode step hands back the recurrent state it was given: the
    K/V of the attention layers advance, ``h`` and the conv taps do not."""
    def frozen(out, cache):
        return out[0], jax.tree_util.tree_map_with_path(
            lambda path, new, old: old if _is_ssd(path) else new,
            out[1], cache)

    _wrap_decode(monkeypatch, frozen)
    assert serve(tmp_path)["correct"] is False


def test_hybrid_token_altered_where_produced_is_caught(tmp_path, monkeypatch):
    _wrap_decode(monkeypatch,
                 lambda out, cache: (jnp.roll(out[0], 1, axis=-1), out[1]))
    assert serve(tmp_path)["correct"] is False


@pytest.mark.parametrize("seed", [1, 2])
def test_hybrid_int8_control_reads_far_above_the_program(tmp_path, seed):
    """The control (the program's int8 weight path over the attention, MLP
    and SSD projections, teacher-forced) at this size reads at least three
    times the sound program's widest gap on the same seed; at the cell's
    widths it is read against the limit (PERF.md gives the readings)."""
    from bench.drivers import serve_hybrid
    from bench.traffic import serving_requests

    cell = harness.find_cell(CELL, hybrid_root(tmp_path))
    cfg, mix = cell.config, cell.traffic
    engine, params = serve_hybrid.build_engine(cfg, seed)
    plan = serving_requests(mix, 1.0, seed, cfg["vocab_size"])
    requests, _ = serve_hybrid.serve_window(engine, plan, lambda now: None,
                                            time.perf_counter)
    check = serve_hybrid.sample_for_check(requests, seed,
                                          mix["check_requests"])
    sound = serve_hybrid.reference_gaps(cfg, params, check).max()
    picks = serve_hybrid.control_tokens(cfg, params, check)
    control = serve_hybrid.reference_gaps(cfg, params, check,
                                          chosen=picks).max()
    assert control > 3 * sound, (sound, control)

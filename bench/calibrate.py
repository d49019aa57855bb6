#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, and the serving cells'
rate sweep.  Not part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 3] [--seconds 10]
    python3 bench/calibrate.py --workload <serving cell> --sweep 5,7,9 \\
        --seconds 30

For each seed it prints one JSON line: the number the cell compares, as a
sound run of the program reads it, and for the first ``--control-seeds``
seeds as the control reads it:

- fit cells: the weights' relative error of one ``fit()`` against the plain
  float32 Fig. A4; the control is that reference computed in bfloat16.
- serving cells: the widest normalised logit gap of a run's sampled served
  tokens under the float32 reference; the control reads, at the same
  positions, the gap of the token that the program's own int8 weight path
  (``quantize="int8"``) puts first.

Everything runs in one process on one set-up where it can: a serving
cell's engine is built once and given each seed's weights.
"""
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _ctx(cell, seed, devices):
    import types
    return types.SimpleNamespace(config=cell.config, traffic=cell.traffic,
                                 chips=cell.chips, seed=seed, devices=devices)


def fit_readings(cell, seeds, control_seeds, devices):
    import jax

    from bench.drivers import fit

    for i, seed in enumerate(seeds):
        ctx = _ctx(cell, seed, devices)
        table, algo, blocks, lr = fit.setup(ctx)
        w = jax.block_until_ready(algo.fit(table).weights)
        del table
        w_ref = fit.reference(ctx, blocks, lr)
        line = {"seed": seed, "program": fit.rel_err(w, w_ref)}
        if i < control_seeds:
            line["control"] = fit.rel_err(
                fit.reference(ctx, blocks, lr, "bfloat16"), w_ref)
        print(json.dumps(line), flush=True)
        del blocks, w


def control_tokens(cfg, params, requests):
    """Per request, the token the program's int8 weight path puts first at
    every served position, teacher-forced over prompt + served tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.layers.embedding import logits_from
    from repro.models.layers.quant import quantize_model_params
    from repro.models.transformer import TransformerLM

    from bench.drivers.serve import arch_config

    model = TransformerLM(arch_config(cfg, quantize="int8"))
    qparams = quantize_model_params(params, "int8")

    @jax.jit
    def first_choice(p, tokens, at):
        h, _ = model.forward_hidden(p, tokens)
        h = jnp.take_along_axis(h, at[None, :, None], axis=1)
        return jnp.argmax(logits_from(p["embed"], h)[0], axis=-1)

    length = cfg["serving"]["max_seq"]
    out = []
    for r in requests:
        served = np.asarray(list(r.out_tokens), np.int32)
        seq = np.concatenate([r.prompt, served[:-1]]).astype(np.int32)
        tokens = np.zeros((1, length), np.int32)
        tokens[0, :len(seq)] = seq
        at = np.zeros(length, np.int32)
        at[:len(served)] = len(r.prompt) - 1 + np.arange(len(served))
        out.append(np.asarray(first_choice(qparams, jnp.asarray(tokens),
                                           jnp.asarray(at)))[:len(served)])
    return out


def serve_readings(cell, seeds, control_seeds, seconds, rates):
    import numpy as np

    from bench.drivers import serve
    from bench.harness import percentile
    from bench.traffic import serving_requests

    cfg, mix = cell.config, dict(cell.traffic)
    engine, params = serve.build_engine(cfg, seeds[0])
    lens = set()
    for rate in rates or [mix["rate_per_s"]]:
        for seed in seeds:
            plan = serving_requests(dict(mix, rate_per_s=rate), seconds, seed,
                                    cfg["vocab_size"])
            lens |= {len(p["prompt"]) for p in plan}
    engine.warmup(prompt_lens=sorted(lens))
    for i, seed in enumerate(seeds):
        if i:
            params = serve.make_params(seed, engine.cfg, cfg["weights"])
            engine.params = params
        for rate in rates or [mix["rate_per_s"]]:
            plan = serving_requests(dict(mix, rate_per_s=rate), seconds, seed,
                                    cfg["vocab_size"])
            t0 = time.perf_counter()
            requests, start = serve.serve_window(
                engine, plan, lambda now: None, time.perf_counter)
            drain = time.perf_counter() - start - seconds
            line = {"seed": seed, "rate_per_s": rate,
                    **serve.window_metrics(requests, start, seconds)}
            third = seconds / 3
            waits = [(r.admitted_at - r.arrival) * 1e3 for r in requests]
            line["queue_ms_p95_first_third"] = percentile(
                [w for w, r in zip(waits, requests) if r.arrival < third], 95)
            line["queue_ms_p95_last_third"] = percentile(
                [w for w, r in zip(waits, requests)
                 if r.arrival >= 2 * third], 95)
            line["offered_tokens_per_s"] = sum(
                p["max_new"] for p in plan) / seconds
            line["drain_s"] = drain
            line["window_and_drain_s"] = time.perf_counter() - t0
            if not rates:
                check = serve.sample_for_check(requests, seed,
                                               mix["check_requests"])
                t1 = time.perf_counter()
                line["program"] = float(np.max(serve.reference_gaps(
                    cfg, params, check)))
                line["reference_s"] = time.perf_counter() - t1
                line["checked_tokens"] = sum(len(r.out_tokens) for r in check)
                if i < control_seeds:
                    picks = control_tokens(cfg, params, check)
                    line["control"] = float(np.max(serve.reference_gaps(
                        cfg, params, check, chosen=picks)))
            print(json.dumps(line), flush=True)


def main():
    import argparse

    import jax

    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--sweep", default="")
    args = ap.parse_args()
    cell = harness.find_cell(args.workload)
    peaks = harness.load_json(ROOT / "bench" / "peaks.json")
    devices = harness.require_devices(cell.chips, peaks, jax.devices())
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    seeds = [int(s) for s in args.seeds.split(",")]
    if cell.driver == "fit":
        fit_readings(cell, seeds, args.control_seeds, devices)
    else:
        rates = [float(r) for r in args.sweep.split(",") if r]
        serve_readings(cell, seeds, args.control_seeds, args.seconds, rates)


if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    main()

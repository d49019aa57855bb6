"""Serving launcher: ``python -m repro.launch.serve --engine lm|model [...]``.

One CLI fronts the whole serving stack:

  * ``--engine lm`` — the continuous-batching LM engine
    (``serve/engine.py`` + ``serve/scheduler.py``): synthetic mixed-length
    prompts arrive on a Poisson/uniform/burst trace, are queued, admitted
    into decode slots (backfilled mid-decode), and greedy-decoded through
    one fused per-slot-position step.  Runs under the serving mesh/rules
    selection (``launch/mesh.host_serving_setup`` — slot sharding over the
    host devices; the production factorization is ``serving_setup``).
  * ``--engine model`` — the classic-ML prediction service
    (``serve/predictor.py``): train a small logreg/k-means on synthetic
    data via the paper's ``Algorithm.train``, then serve feature-block
    requests through the shard-aware microbatcher.

The jitted prefill/decode (or the compiled predict) is **warmed up before
the timed run**, so the perf report measures serving, not compilation.
Both engines end with a queue-depth/latency report; ``--json`` emits it as
a ``RESULT::{json}`` line like the other launchers.

Examples (CPU):
    PYTHONPATH=src python -m repro.launch.serve --engine lm --arch qwen2-1.5b \
        --smoke --requests 8 --slots 4 --prompt-lens 8,12,16,20 --max-new 8
    PYTHONPATH=src python -m repro.launch.serve --engine lm --arch qwen2-1.5b \
        --smoke --requests 8 --arrival poisson --rate 4 --json
    PYTHONPATH=src python -m repro.launch.serve --engine lm --arch qwen2-1.5b \
        --smoke --requests 16 --prefix-cache on --prefix-share 0.8 \
        --prefix-len 32 --prefix-block 16 --json
    PYTHONPATH=src python -m repro.launch.serve --engine model \
        --algorithm kmeans --rows 512 --features 16 --batch 64 --json
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.launch.compile_cache import enable_compile_cache


# --------------------------------------------------------------------------- #
# arrival traces
# --------------------------------------------------------------------------- #
def arrival_trace(kind: str, n: int, rate: float, seed: int) -> np.ndarray:
    """Request release times (seconds from serve start).

    ``all-at-once`` (rate<=0 or kind 'none') releases everything at t=0;
    ``poisson`` draws exponential inter-arrivals at ``rate`` req/s;
    ``uniform`` spaces arrivals evenly at the same mean rate; ``burst``
    releases half at t=0 and half at a *fixed* ``1/rate`` seconds — one
    mean inter-arrival gap, independent of ``n``.  (The old offset was
    ``0.5/rate * n``: it grew with the trace length, so large traces
    degenerated into two disjoint static batches that never overlapped in
    the slot table and inflated the continuous-batching backfill win.
    Pinned by ``tests/test_arrival_traces.py``.)
    """
    if kind == "none" or rate <= 0:
        return np.zeros(n)
    rng = np.random.default_rng(seed + 1)
    if kind == "poisson":
        return np.cumsum(rng.exponential(1.0 / rate, size=n))
    if kind == "uniform":
        return np.arange(n) / rate
    if kind == "burst":
        half = (n + 1) // 2
        return np.concatenate([np.zeros(half), np.full(n - half, 1.0 / rate)])
    raise ValueError(f"unknown arrival kind {kind!r}")


# --------------------------------------------------------------------------- #
# --engine lm
# --------------------------------------------------------------------------- #
def run_lm(args) -> dict:
    import dataclasses as _dc

    from repro.launch.mesh import host_serving_setup
    from repro.models.transformer import init_model
    from repro.serve import (QueueAutoscaler, RadixPrefixCache, ReplicaRouter,
                             Request, ServeEngine, SlotScheduler)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.encoder_layers or cfg.vision_tokens:
        print(f"note: {cfg.name} frontend is stubbed; serving text-only path")
    if args.quantize != "none":
        cfg = _dc.replace(cfg, quantize=args.quantize)
    params, axes = init_model(jax.random.PRNGKey(args.seed), cfg)
    fleet = args.replicas > 0
    mesh = rules = param_axes = None
    if args.mesh:
        if fleet:
            raise SystemExit("--mesh and --replicas are mutually exclusive "
                             "(the fleet shards lanes, not params)")
        mesh, rules = host_serving_setup(cfg)
        param_axes = axes

    lens = [int(x) for x in args.prompt_lens.split(",") if x]
    rng = np.random.default_rng(args.seed)
    arrivals = arrival_trace(args.arrival, args.requests, args.rate, args.seed)
    tenants = [f"t{i}" for i in range(max(1, args.tenants))]
    # with --prefix-share p, fraction p of the requests open with ONE shared
    # --prefix-len token prefix (a synthetic system prompt); the rest are
    # fully random at the SAME total length, so cache-on vs cache-off runs
    # and shared vs unshared requests all prefill identical token counts
    shared_prefix = rng.integers(0, cfg.vocab_size,
                                 size=args.prefix_len).astype(np.int32)

    def _prompt(i: int) -> np.ndarray:
        n = args.prefix_len + lens[i % len(lens)]
        if args.prefix_share > 0 and rng.random() < args.prefix_share:
            tail = rng.integers(0, cfg.vocab_size,
                                size=n - args.prefix_len).astype(np.int32)
            return np.concatenate([shared_prefix, tail])
        return rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)

    reqs = [Request(prompt=_prompt(i) if args.prefix_share > 0
                    else rng.integers(0, cfg.vocab_size,
                                      size=lens[i % len(lens)]
                                      ).astype(np.int32),
                    max_new_tokens=args.max_new, arrival=float(arrivals[i]),
                    tenant=tenants[i % len(tenants)],
                    slo_ms=args.slo_ms if args.slo_ms > 0 else None)
            for i in range(args.requests)]
    prefix_cache = (RadixPrefixCache(block_size=args.prefix_block,
                                     capacity_blocks=args.prefix_capacity)
                    if args.prefix_cache == "on" else None)

    def _prefix_fields(rep: dict) -> None:
        s = prefix_cache.stats() if prefix_cache is not None else None
        rep["prefix_cache"] = s
        rep["prefill_tokens"] = (s["prompt_tokens"] if s
                                 else sum(len(r.prompt) for r in reqs))
        rep["cached_prefill_tokens"] = s["cached_tokens"] if s else 0
        rep["prefix_hit_rate"] = s["hit_rate"] if s else 0.0
        if s:
            print(f"  prefix cache: {s['cached_tokens']}/{s['prompt_tokens']} "
                  f"prefill tokens served cached (hit rate "
                  f"{s['hit_rate']:.2f}), {s['evictions']} evictions")

    if fleet:
        autoscaler = None
        if args.autoscale_min > 0:
            autoscaler = QueueAutoscaler(
                slots_per_replica=args.slots,
                min_replicas=args.autoscale_min,
                max_replicas=args.replicas)
        router = ReplicaRouter(
            cfg, params, slots_per_replica=args.slots,
            max_replicas=args.replicas, max_seq=args.max_seq,
            admission=args.admission, autoscaler=autoscaler,
            min_replicas=args.autoscale_min or args.replicas,
            prefix_cache=prefix_cache)
        if not args.no_warmup:
            t0 = time.perf_counter()
            spans = (range(args.autoscale_min, args.replicas + 1)
                     if autoscaler else [args.replicas])
            router.warmup(prompt_lens=lens, spans=spans)
            print(f"warmup (compile) {time.perf_counter() - t0:.2f}s — "
                  "excluded from the perf report")
        start = time.perf_counter()
        done = router.run(reqs, now_fn=lambda: time.perf_counter() - start)
        dt = time.perf_counter() - start
        served = [r for r in done if r.done]
        total_new = sum(len(r.out_tokens) for r in served)
        rep = router.report()
        rep.pop("per_replica")
        rep.update({
            "engine": "lm", "arch": args.arch, "slots": args.slots,
            "requests": len(served), "new_tokens": total_new,
            "seconds": round(dt, 4),
            "requests_per_sec": round(len(served) / dt, 2),
            "tokens_per_sec": round(total_new / dt, 1),
            "arrival": args.arrival, "rate": args.rate,
            "quantize": args.quantize, "admission": args.admission,
            "tenants_n": len(tenants), "mesh": "none",
        })
        print(f"fleet served {len(served)}/{len(done)} requests / "
              f"{total_new} tokens in {dt:.2f}s "
              f"({rep['requests_per_sec']} req/s, "
              f"{rep['tokens_per_sec']} tok/s) | "
              f"{args.replicas}x{args.slots} lanes, quantize={args.quantize}")
        print(f"latency p50={rep['latency_p50']*1e3:.1f}ms "
              f"p95={rep['latency_p95']*1e3:.1f}ms "
              f"p99={rep['latency_p99']*1e3:.1f}ms | "
              f"rejected={rep['rejected']} degraded={rep['degraded']} | "
              f"backfills={rep['backfills']}")
        for t, tr in sorted(rep["tenants"].items()):
            print(f"  {t}: finished={tr['finished']} rejected={tr['rejected']}"
                  f" slo_attainment={tr['slo_attainment']:.2f}")
        if rep["autoscaler_events"]:
            print(f"  autoscaler: {rep['autoscaler_events']}")
        _prefix_fields(rep)
        assert all(r.done or r.rejected for r in done)
        return rep

    engine = ServeEngine(cfg, params, batch_size=args.slots,
                         max_seq=args.max_seq, mesh=mesh, rules=rules,
                         param_axes=param_axes, prefix_cache=prefix_cache)
    if not args.no_warmup:
        t0 = time.perf_counter()
        engine.warmup(prompt_lens=lens)
        print(f"warmup (compile) {time.perf_counter() - t0:.2f}s — "
              "excluded from the perf report")

    sched = SlotScheduler(args.slots)
    start = time.perf_counter()
    now_fn = (lambda: time.perf_counter() - start)
    done = engine.run(reqs, scheduler=sched, now_fn=now_fn)
    dt = time.perf_counter() - start

    total_new = sum(len(r.out_tokens) for r in done)
    rep = sched.report()
    rep.update({
        "engine": "lm", "arch": args.arch, "slots": args.slots,
        "requests": len(done), "new_tokens": total_new,
        "seconds": round(dt, 4),
        "requests_per_sec": round(len(done) / dt, 2),
        "tokens_per_sec": round(total_new / dt, 1),
        "arrival": args.arrival, "rate": args.rate,
        "quantize": args.quantize,
        "mesh": (f"{tuple(mesh.devices.shape)}" if mesh is not None
                 else "none"),
        "ragged_prefill": engine.ragged_ok,
    })
    _prefix_fields(rep)
    print(f"served {len(done)} requests / {total_new} tokens in {dt:.2f}s "
          f"({rep['requests_per_sec']} req/s, {rep['tokens_per_sec']} tok/s)")
    print(f"queue depth max={rep['queue_depth_max']} "
          f"mean={rep['queue_depth_mean']:.2f} | backfills={rep['backfills']} "
          f"| wait p50={rep['wait_p50']*1e3:.1f}ms p95={rep['wait_p95']*1e3:.1f}ms "
          f"| latency p50={rep['latency_p50']*1e3:.1f}ms "
          f"p95={rep['latency_p95']*1e3:.1f}ms "
          f"p99={rep['latency_p99']*1e3:.1f}ms")
    for i, r in enumerate(done[:4]):
        print(f"  req{i}: {r.out_tokens[:8]}...")
    assert all(r.done for r in done)
    return rep


# --------------------------------------------------------------------------- #
# --engine model
# --------------------------------------------------------------------------- #
def run_model(args) -> dict:
    from repro.core.numeric_table import MLNumericTable
    from repro.serve import ModelPredictor, PredictRequest

    rng = np.random.default_rng(args.seed)
    if args.algorithm == "logreg":
        from repro.core.algorithms.logistic_regression import (
            LogisticRegressionAlgorithm, LogisticRegressionParameters)
        w = np.linspace(-1, 1, args.features).astype(np.float32)
        X = rng.normal(size=(args.rows, args.features)).astype(np.float32)
        y = (X @ w > 0).astype(np.float32)
        table = MLNumericTable.from_numpy(
            np.concatenate([y[:, None], X], 1), num_shards=args.shards)
        model = LogisticRegressionAlgorithm(max_iter=5).fit(table)
    else:
        from repro.core.algorithms.kmeans import KMeans, KMeansParameters
        k = 4
        centers = np.stack([np.full(args.features, 2.5 * (i - (k - 1) / 2))
                            for i in range(k)]).astype(np.float32)
        X = (centers[rng.integers(0, k, size=args.rows)]
             + 0.3 * rng.normal(size=(args.rows, args.features))
             ).astype(np.float32)
        table = MLNumericTable.from_numpy(X, num_shards=args.shards)
        model = KMeans(KMeansParameters(
            k=k, max_iter=5, use_kernel=args.kernel)).fit(table)

    service = ModelPredictor(model, max_batch=args.batch,
                             num_shards=args.shards)
    # request stream: feature blocks of mixed sizes
    sizes = rng.integers(1, max(2, args.batch // 2), size=args.requests)
    blocks = [rng.normal(size=(int(s), args.features)).astype(np.float32)
              for s in sizes]

    # warm the compiled predict before timing
    if not args.no_warmup:
        service.predict_many([blocks[0]])
        service.batches = service.rows_served = service.rows_padded = 0

    arrivals = arrival_trace(args.arrival, args.requests, args.rate, args.seed)
    start = time.perf_counter()
    for b, a in zip(blocks, arrivals):
        wait = a - (time.perf_counter() - start)
        if wait > 0:
            time.sleep(wait)
        service.submit(PredictRequest(features=b, arrival=float(a)))
    done = service.flush(now=time.perf_counter() - start)
    dt = time.perf_counter() - start

    rows = sum(b.shape[0] for b in blocks)
    rep = service.report()
    rep.update({
        "engine": "model", "algorithm": args.algorithm,
        "requests": len(done), "rows": rows, "seconds": round(dt, 4),
        "rows_per_sec": round(rows / dt, 1),
        "requests_per_sec": round(len(done) / dt, 2),
    })
    print(f"served {len(done)} predict requests / {rows} rows in {dt:.3f}s "
          f"({rep['rows_per_sec']} rows/s, {rep['batches']} microbatches, "
          f"pad fraction {rep['pad_fraction']:.2f})")
    assert all(r.done and r.result is not None for r in done)
    return rep


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--engine", default="lm", choices=("lm", "model"))
    # shared
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arrival", default="none",
                    choices=("none", "poisson", "uniform", "burst"))
    ap.add_argument("--rate", type=float, default=0.0,
                    help="mean arrival rate (requests/sec; 0 = all at once)")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip compile warmup (the report then includes "
                         "compile time)")
    ap.add_argument("--json", action="store_true",
                    help="print a RESULT::{json} line with the perf report")
    # lm engine
    ap.add_argument("--arch", default="qwen2-1.5b", choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots (continuous-batching batch size; "
                         "slots PER REPLICA with --replicas)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="serve through a ReplicaRouter fleet of this many "
                         "replicas (0 = single engine)")
    ap.add_argument("--tenants", type=int, default=1,
                    help="synthetic tenants (requests round-robin t0..tN-1)")
    ap.add_argument("--slo-ms", type=float, default=0.0,
                    help="per-request arrival→finish SLO in ms (0 = none)")
    ap.add_argument("--admission", default="none",
                    choices=("none", "reject", "degrade"),
                    help="fleet admission control when the predicted "
                         "completion misses the SLO")
    ap.add_argument("--quantize", default="none",
                    choices=("none", "bf16", "int8"),
                    help="weight quantization for the decode/prefill path")
    ap.add_argument("--prefix-cache", default="off", choices=("on", "off"),
                    help="radix prefix KV cache: reuse repeated prompt "
                         "prefixes across requests (and replicas)")
    ap.add_argument("--prefix-block", type=int, default=16,
                    help="prefix cache block size in tokens")
    ap.add_argument("--prefix-capacity", type=int, default=256,
                    help="prefix cache capacity in blocks (LRU beyond)")
    ap.add_argument("--prefix-share", type=float, default=0.0,
                    help="fraction of requests opening with one shared "
                         "--prefix-len token prefix (synthetic system "
                         "prompt); the rest are random at the same length")
    ap.add_argument("--prefix-len", type=int, default=32,
                    help="shared prefix length for --prefix-share traffic")
    ap.add_argument("--autoscale-min", type=int, default=0,
                    help="enable queue-driven autoscale with this minimum "
                         "replica count (0 = fixed fleet)")
    ap.add_argument("--prompt-lens", default="8,12,16,20",
                    help="comma list; request i uses length i mod list")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--mesh", action="store_true",
                    help="run under host_serving_setup (slot sharding over "
                         "host devices)")
    # model engine
    ap.add_argument("--algorithm", default="logreg",
                    choices=("logreg", "kmeans"))
    ap.add_argument("--rows", type=int, default=256)
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--batch", type=int, default=64,
                    help="microbatch rows (compiled predict shape)")
    ap.add_argument("--shards", type=int, default=1)
    ap.add_argument("--kernel", action="store_true",
                    help="kmeans: route assignment through the Pallas kernel")
    args = ap.parse_args()
    enable_compile_cache()

    rep = run_lm(args) if args.engine == "lm" else run_model(args)
    if args.json:
        print("RESULT::" + json.dumps(rep))


if __name__ == "__main__":
    main()

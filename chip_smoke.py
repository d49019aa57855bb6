#!/usr/bin/env python3
"""Run the system's two main paths once on a TPU and check what comes out.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --four-chips   # four chips: phase A's schedules only

Phase A, the MLI estimator path (paper §IV-A, Fig. A4).  Logistic
regression through ``DistributedRunner`` at one machine's share of the
paper's run, which was 200K rows x 160K features on 32 machines: 6,144 rows x
160,000 features in float32 (3.93 GB on the device), label in column 0, four
partitions.  The resident fit is compared with an independent float64 NumPy
Fig. A4; the streaming fit runs through ``repro.launch.fit``; the Pallas
logistic-regression gradient runs at the paper's width against float64.

Phase B, serving.  qwen2-1.5b at its published widths with nothing cut (28
layers, d_model 1536, 12 heads / 2 KV heads, d_ff 8960, vocab 151,936, bf16;
random weights from ``--seed``) through ``ServeEngine`` + ``SlotScheduler``
as ``repro.launch.serve`` drives them: warmup, then ``engine.run``.  The
engine's first-token logits are checked against ``TransformerLM.forward``.

``--four-chips`` runs phase A's resident fit alone, weak-scaled to 24,576
rows (6,144 per chip) on a ``("data",)`` mesh, once per collective schedule,
and compares the schedules with each other and with float64.

Each phase prints its own lines.  Times are printed for information and are
not a benchmark.  The last line is ``{"ok": true, "device": {...}}``; the
script exits nonzero without printing it when JAX finds no TPU or when any
check fails.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import pathlib
import re
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# Phase A.  6,144 rows, not the 6,250 of an even 200K/32 split, so that the
# four partitions hold 48 minibatches of 32 rows each.
ROWS_PER_CHIP = 6_144
FEATURES = 160_000
PARTITIONS = 4
LOCAL_BATCH = 32
ROUNDS = 2
HOLDOUT = 512
# Step size per unit of feature width: with non-negative features the margin
# of a row grows with the feature count, and 64/d keeps it of order one.
LR_TIMES_FEATURES = 64.0
KERNEL_ROWS = 2_048
SCHEDULES = ("gather_broadcast", "allreduce", "reduce_scatter")

# The resident fit's margins x·w are f32 dots, which the TPU runs at its
# default precision: one bf16 pass (inputs rounded to 8 mantissa bits, f32
# accumulation).  w is dominated by one sign, so the rounding errors of the
# 160,000 products cancel to ~2^-9/sqrt(d) of a margin; replaying Fig. A4 in
# NumPy with bf16-rounded dots gives a weight error of about 1e-5 of max|w|.
# 1e-3 leaves two orders for f32 reduction order, and a dropped minibatch or
# a wrong combine moves the weights by 1e-2 or more.
TOL_WEIGHTS = 1e-3
# Schedules only reorder the f32 sums of the combine.
TOL_SCHEDULES = 1e-5
# The kernel's gradient Xᵀz sums 2,048 rows of mixed sign: bf16 rounding of
# its inputs leaves ~2^-9 of max|g|; 1e-2 is five times that.
TOL_KERNEL = 1e-2

# Phase B.
ARCH = "qwen2-1.5b"
SLOTS = 4
PROMPT_LENS = (128, 1024, 333, 901, 517, 200, 640, 777)
MAX_NEW = 32
MAX_SEQ = 2048
# Engine and forward both run bf16 (8 mantissa bits) through 28 layers in
# different shapes (a ragged padded wave of 4 vs one batch of 8), so their
# roundings differ at every layer: on a v5e they measured 7.3e-2 of
# max|logit| apart.  0.15 is about twice that; a wrong mask, position or
# cache moves the logits by the order of max|logit| itself.
TOL_LOGITS = 0.15
# What bf16 costs is measured in each run by a float32 twin of forward (the
# same weights upcast, matmuls at HIGHEST): the engine may be no further from
# it than this multiple of forward's own distance from it, plus a slack for
# float32 reordering where the model already runs in float32.
TWIN_FACTOR, TWIN_SLACK = 2.0, 1e-3
# Greedy tokens from random weights have near-tied logits, so bf16 noise can
# flip some; a broken decode step agrees on almost none of 151,936 tokens.
MIN_TOKEN_AGREEMENT = 0.5


class CompileMeter:
    """Seconds spent compiling (or loading from the persistent cache) and
    persistent-cache hits and misses, from ``jax.monitoring`` events."""

    def __init__(self):
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self) -> str:
        """Report and reset."""
        out = (f"compile {self.seconds:.2f}s (persistent cache: "
               f"{self.hits} hits, {self.misses} misses)")
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        return out


def peak_memory() -> str:
    """The device's peak bytes in use so far in this process."""
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 1e9:.2f} GB"


def check(ok: bool, what: str) -> None:
    print(f"  check: {what}: {'pass' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


def require_tpu(count: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{devices[0].platform!r}")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: needs {count} chips; JAX found "
                         f"{len(devices)}")
    return devices


# --------------------------------------------------------------------------- #
# phase A: the estimator path
# --------------------------------------------------------------------------- #
def _sigmoid(m: np.ndarray) -> np.ndarray:
    return 0.5 * (1.0 + np.tanh(0.5 * m))


def fig_a4_float64(table: np.ndarray, lr: float, rounds: int,
                   partitions: int, local_batch: int) -> np.ndarray:
    """Paper Fig. A4 in float64 NumPy, written apart from the code under
    test: every round, each of ``partitions`` contiguous row blocks runs
    sequential SGD in minibatches of ``local_batch`` from the current
    weights, then the block weights are averaged.  The float32 table is
    read one minibatch at a time, so no float64 copy of it is made."""
    n, width = table.shape
    per = n // partitions
    w = np.zeros(width - 1)
    for _ in range(rounds):
        parts = []
        for p in range(partitions):
            wp = w.copy()
            for start in range(p * per, (p + 1) * per, local_batch):
                mb = table[start:start + local_batch].astype(np.float64)
                x, y = mb[:, 1:], mb[:, 0]
                wp -= lr * (x.T @ (_sigmoid(x @ wp) - y)) / len(y)
            parts.append(wp)
        w = np.mean(parts, axis=0)
    return w


def _logreg_data(rows: int, features: int, seed: int):
    """(table (rows, 1+features) with the label in column 0, holdout X,
    holdout y), drawn in float32 so no float64 table is ever built."""
    from repro.data.synthetic import synth_imagenet_features

    X, y = synth_imagenet_features(rows + HOLDOUT, features, seed=seed,
                                   dtype=np.float32)
    table = np.empty((rows, features + 1), np.float32)
    table[:, 0] = y[:rows]
    table[:, 1:] = X[:rows]
    return table, X[rows:].copy(), y[rows:].copy()


def _rel_err(a: np.ndarray, ref: np.ndarray) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64) - ref))
                 / np.max(np.abs(ref)))


def _fit_twice(algo, table, meter: CompileMeter, tag: str):
    """Fit twice: the first pays tracing and compilation, the second finds
    the program in the persistent cache (when its compile was long enough
    to be written there)."""
    w = None
    for i in (1, 2):
        t0 = time.perf_counter()
        w = jax.block_until_ready(algo.fit(table).weights)
        print(f"  {tag} fit {i}: {time.perf_counter() - t0:.2f}s wall, "
              f"{meter.take()} (information only, not a benchmark)",
              flush=True)
    return w


def phase_a(meter: CompileMeter, *, rows: int = ROWS_PER_CHIP,
            features: int = FEATURES, seed: int = 0,
            stream_rows: int = 512, stream_epochs: int = 3) -> None:
    from repro.core.algorithms.logistic_regression import (
        LogisticRegressionAlgorithm)
    from repro.core.numeric_table import MLNumericTable
    from repro.kernels import ops as kops
    from repro.launch import fit as fit_cli

    lr = LR_TIMES_FEATURES / features
    print(f"[A] logistic regression, resident: {rows} rows x {features} "
          f"features f32, {PARTITIONS} partitions on one device, "
          f"{ROUNDS} rounds of local SGD (minibatch {LOCAL_BATCH}, "
          f"lr {lr:.3g})", flush=True)
    t0 = time.perf_counter()
    host, Xh, yh = _logreg_data(rows, features, seed)
    table = MLNumericTable.from_numpy(host, num_shards=PARTITIONS)
    print(f"  data: {host.nbytes / 1e9:.2f} GB table made and placed in "
          f"{time.perf_counter() - t0:.2f}s", flush=True)
    algo = LogisticRegressionAlgorithm(
        learning_rate=lr, max_iter=ROUNDS, local_batch_size=LOCAL_BATCH)
    print("  kernel path: none; the gradient closure is jnp that XLA fuses "
          "(use_kernel=False)")
    w = np.asarray(_fit_twice(algo, table, meter, "resident"))
    t0 = time.perf_counter()
    w_ref = fig_a4_float64(host, lr, ROUNDS, PARTITIONS, LOCAL_BATCH)
    err = _rel_err(w, w_ref)
    print(f"  float64 reference in {time.perf_counter() - t0:.2f}s: "
          f"max|w - w_ref| / max|w_ref| = {err:.3e} (tolerance "
          f"{TOL_WEIGHTS:g})", flush=True)
    check(bool(np.all(np.isfinite(w))) and err <= TOL_WEIGHTS,
          "resident weights match float64 Fig. A4")
    acc = float(np.mean((Xh @ w > 0) == (yh > 0.5)))
    acc_ref = float(np.mean((Xh @ w_ref > 0) == (yh > 0.5)))
    print(f"  holdout accuracy {acc:.4f} on {HOLDOUT} rows (float64 "
          f"reference {acc_ref:.4f})")

    n_k = min(KERNEL_ROWS, rows)
    g = kops.logreg_grad(table.data[:n_k, 1:], table.data[:n_k, 0],
                         jnp.asarray(w, jnp.float32))
    pad = -features % 512
    path = (f"logreg_grad Pallas kernel, {features} columns zero-padded to "
            f"{features + pad}" if kops.on_tpu()
            else "jnp reference (off TPU)")
    x64 = host[:n_k, 1:].astype(np.float64)
    g_ref = x64.T @ (_sigmoid(x64 @ w.astype(np.float32).astype(np.float64))
                     - host[:n_k, 0])
    del x64
    kerr = _rel_err(np.asarray(g), g_ref)
    print(f"  kernel path: {path}; gradient of {n_k} rows: "
          f"max|g - g_ref| / max|g_ref| = {kerr:.3e} (tolerance "
          f"{TOL_KERNEL:g}), {meter.take()}", flush=True)
    check(kerr <= TOL_KERNEL, "Pallas gradient matches float64")
    del table, g, host

    argv = ["--algorithm", "logreg", "--features", str(features),
            "--rows-per-epoch", str(stream_rows), "--epochs",
            str(stream_epochs), "--chunks-per-epoch", "4", "--num-shards",
            str(PARTITIONS), "--local-batch-size", "8", "--lr", f"{lr:g}",
            "--seed", str(seed)]
    print(f"[A] logistic regression, streaming: repro.launch.fit "
          f"{' '.join(argv)}", flush=True)
    out = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(out):
        fit_cli.main(argv)
    wall = time.perf_counter() - t0
    for line in out.getvalue().splitlines():
        print(f"  | {line}")
    print(f"  streaming fit: {wall:.2f}s wall, {meter.take()} (information "
          f"only, not a benchmark)", flush=True)
    m = re.search(r"done: holdout loss (\S+) acc (\S+)", out.getvalue())
    check(m is not None and np.isfinite(float(m.group(1)))
          and 0.0 <= float(m.group(2)) <= 1.0
          and f"stream position: step {stream_epochs}" in out.getvalue(),
          f"streaming fit ran {stream_epochs} epochs to a finite holdout loss")
    print(f"  peak device memory so far: {peak_memory()}")


def phase_a_four_chips(meter: CompileMeter, *, chips: int = 4,
                       rows_per_chip: int = ROWS_PER_CHIP,
                       features: int = FEATURES, seed: int = 0) -> None:
    from repro.core.algorithms.logistic_regression import (
        LogisticRegressionAlgorithm)
    from repro.core.compat import make_mesh
    from repro.core.numeric_table import MLNumericTable

    rows = rows_per_chip * chips
    lr = LR_TIMES_FEATURES / features
    mesh = make_mesh((chips,), ("data",))
    print(f"[A4] logistic regression, weak scaling: {rows} rows x {features} "
          f"features f32 on a {chips}-chip ('data',) mesh, {ROUNDS} rounds "
          f"(minibatch {LOCAL_BATCH}, lr {lr:.3g})", flush=True)
    t0 = time.perf_counter()
    host, Xh, yh = _logreg_data(rows, features, seed)
    table = MLNumericTable.from_numpy(host, mesh=mesh)
    print(f"  data: {host.nbytes / 1e9:.2f} GB table made and placed in "
          f"{time.perf_counter() - t0:.2f}s", flush=True)
    shards = table.data.addressable_shards
    check(len({s.device for s in shards}) == chips
          and all(s.data.shape == (rows // chips, features + 1)
                  for s in shards),
          f"each of {chips} chips holds its own {rows // chips} rows")
    weights = {}
    for sched in SCHEDULES:
        algo = LogisticRegressionAlgorithm(
            learning_rate=lr, max_iter=ROUNDS, local_batch_size=LOCAL_BATCH,
            schedule=sched)
        weights[sched] = np.asarray(_fit_twice(algo, table, meter, sched))
    w_ref = fig_a4_float64(host, lr, ROUNDS, chips, LOCAL_BATCH)
    base = weights[SCHEDULES[0]]
    for sched, w in weights.items():
        err = _rel_err(w, w_ref)
        drift = _rel_err(w, base.astype(np.float64))
        print(f"  {sched}: vs float64 {err:.3e} (tolerance {TOL_WEIGHTS:g}), "
              f"vs {SCHEDULES[0]} {drift:.3e} (tolerance {TOL_SCHEDULES:g}), "
              f"holdout accuracy {np.mean((Xh @ w > 0) == (yh > 0.5)):.4f}",
              flush=True)
        check(bool(np.all(np.isfinite(w))) and err <= TOL_WEIGHTS
              and drift <= TOL_SCHEDULES,
              f"{sched} agrees with float64 and with {SCHEDULES[0]}")
    print(f"  peak memory of chip 0 so far: {peak_memory()}")


# --------------------------------------------------------------------------- #
# phase B: serving
# --------------------------------------------------------------------------- #
def _prefill_wave_logits(engine, prompts, pad_to: int = 8) -> np.ndarray:
    """The logits the engine takes first tokens from for one admission
    wave: its own compiled ragged prefill, fed as ``_prefill_into`` feeds
    it (sequence padded to a multiple of ``pad_to``, batch to the slots)."""
    S = -(-max(len(p) for p in prompts) // pad_to) * pad_to
    tokens = np.zeros((engine.batch, S), np.int32)
    lens = np.ones(engine.batch, np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
        lens[i] = len(p)
    logits, _ = engine._prefill_ragged(
        engine.params, jnp.asarray(tokens), jnp.asarray(lens))
    return np.asarray(logits[:len(prompts), -1], np.float32)


def phase_b(meter: CompileMeter, cfg=None, *, seed: int = 0,
            prompt_lens=PROMPT_LENS, max_new: int = MAX_NEW,
            max_seq: int = MAX_SEQ, slots: int = SLOTS) -> None:
    from repro.configs import get_config
    from repro.models.layers.embedding import logits_from
    from repro.models.transformer import TransformerLM, init_model
    from repro.serve import Request, ServeEngine, SlotScheduler

    cfg = cfg or get_config(ARCH)
    print(f"[B] serving {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads} heads / {cfg.num_kv_heads} KV, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}; "
          f"{len(prompt_lens)} requests, prompts {min(prompt_lens)}-"
          f"{max(prompt_lens)}, max_new {max_new}, max_seq {max_seq}, "
          f"{slots} slots", flush=True)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_model(jax.random.PRNGKey(seed), cfg)[0])
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"  weights: {n_bytes / 1e9:.2f} GB from PRNGKey({seed}) in "
          f"{time.perf_counter() - t0:.2f}s, {meter.take()}", flush=True)
    engine = ServeEngine(cfg, params, batch_size=slots, max_seq=max_seq)
    print(f"  kernel path: none; attention, norms and matmuls are jnp that "
          f"XLA compiles (quantize={cfg.quantize}, ragged prefill "
          f"{engine.ragged_ok})")
    t0 = time.perf_counter()
    engine.warmup(prompt_lens=prompt_lens)
    print(f"  warmup: {time.perf_counter() - t0:.2f}s wall, {meter.take()}",
          flush=True)

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in prompt_lens]
    reqs = [Request(prompt=p, max_new_tokens=max_new) for p in prompts]
    sched = SlotScheduler(slots)
    start = time.perf_counter()
    done = engine.run(reqs, scheduler=sched,
                      now_fn=lambda: time.perf_counter() - start)
    wall = time.perf_counter() - start
    new_tokens = sum(len(r.out_tokens) for r in done)
    print(f"  served {len(done)} requests / {new_tokens} tokens in "
          f"{wall:.2f}s, {meter.take()} (information only, not a benchmark)",
          flush=True)
    check(all(r.done and len(r.out_tokens) == max_new for r in done),
          f"all {len(done)} requests finished with {max_new} tokens")

    engine_logits = np.concatenate(
        [_prefill_wave_logits(engine, prompts[i:i + slots])
         for i in range(0, len(prompts), slots)])
    # teacher-forced reference: one forward over every prompt followed by
    # its generated tokens, right-padded (causal, so padding changes nothing
    # before it); logits at position len-1+j predict generated token j
    seqs = [np.concatenate([r.prompt, np.asarray(r.out_tokens, np.int32)])
            for r in done]
    width = -(-max(len(s) for s in seqs) // 8) * 8
    tokens = np.zeros((len(seqs), width), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
    at = np.asarray([len(r.prompt) - 1 + np.arange(max_new) for r in done],
                    np.int32)

    # TransformerLM.forward is logits_from(forward_hidden(...)); the vocab
    # projection is taken only at the compared positions, since full-vocab
    # logits for every position would take about 5 GB
    def reference(model, upcast: bool):
        @jax.jit
        def run(params, tokens, at):
            if upcast:
                params = jax.tree.map(lambda x: x.astype(jnp.float32), params)
            h, _ = model.forward_hidden(params, tokens)
            h = jnp.take_along_axis(h, at[:, :, None], axis=1)
            return logits_from(params["embed"], h)
        return run

    ref = np.asarray(reference(engine.model, False)(
        engine.params, jnp.asarray(tokens), jnp.asarray(at)), np.float32)
    first = at[:, :1]
    twin = TransformerLM(dataclasses.replace(cfg, dtype="float32"))
    with jax.default_matmul_precision("highest"):
        ref32 = np.asarray(reference(twin, True)(
            engine.params, jnp.asarray(tokens), jnp.asarray(first)))[:, 0]

    def rel(a, b):
        return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))

    diff = rel(engine_logits, ref[:, 0])
    fwd_err, eng_err = rel(ref[:, 0], ref32), rel(engine_logits, ref32)
    greedy = np.asarray([r.out_tokens for r in done])
    agree = float(np.mean(greedy == np.argmax(ref, axis=-1)))
    print(f"  first-token logits, engine prefill vs TransformerLM.forward: "
          f"max|diff| / max|ref| = {diff:.3e} (tolerance {TOL_LOGITS:g}), "
          f"{meter.take()}", flush=True)
    print(f"  against forward's float32 twin: forward {fwd_err:.3e}, engine "
          f"{eng_err:.3e} (tolerance {TWIN_FACTOR:g} x forward's + "
          f"{TWIN_SLACK:g})")
    print(f"  greedy tokens equal to the teacher-forced forward argmax: "
          f"{int(round(agree * greedy.size))}/{greedy.size} ({agree:.3f})",
          flush=True)
    check(bool(np.all(np.isfinite(engine_logits))) and diff <= TOL_LOGITS,
          "engine first-token logits match forward")
    check(eng_err <= TWIN_FACTOR * fwd_err + TWIN_SLACK,
          "engine is as close to float32 as forward is")
    check(agree >= MIN_TOKEN_AGREEMENT,
          f"at least {MIN_TOKEN_AGREEMENT:.0%} of greedy tokens agree")
    print(f"  peak device memory so far: {peak_memory()}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only phase A's resident fit on a 4-chip mesh, "
                         "once per collective schedule")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    chips = 4 if args.four_chips else 1
    devices = require_tpu(chips)
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}; compile cache {cache}", flush=True)
    meter = CompileMeter()
    t0 = time.perf_counter()
    if args.four_chips:
        phase_a_four_chips(meter, chips=chips, seed=args.seed)
    else:
        phase_a(meter, seed=args.seed)
        phase_b(meter, seed=args.seed)
    print(f"total {time.perf_counter() - t0:.2f}s (information only, not a "
          f"benchmark)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()

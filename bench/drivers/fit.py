"""Driver for the MLI estimator cells: ``LogisticRegressionAlgorithm.fit``
on a device-resident table, called again and again through the window.

Set-up makes the table on the device from the seed (one jitted call; each
chip makes its own rows), wraps it in an ``MLNumericTable`` (one chip: the
traffic's partitions emulated on it; several chips: one partition per chip
on a ``("data",)`` mesh), and fits once, which compiles the round program
and puts it in the persistent cache.  The window then calls ``fit()`` as a
user does, each call inside a ``bench.fit`` host span, until ``seconds``
have passed; every call is timed to its ``block_until_ready``.

``fit_rows_per_s`` is table rows x rounds x completed fits over the whole
window.  Every fit of the window answers the same question, so every
answer is compared with one plain Fig. A4 (``bench/reference/fig_a4.py``)
computed after the window closes, at float32 ``HIGHEST`` precision.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.fig_a4 import fig_a4

# E[max(z, 0)] for z ~ N(0, 1): the planted margin's mean per unit of sum(w),
# so the label splits rows about evenly, as the median does in
# repro.data.synthetic.synth_imagenet_features
_RELU_MEAN = 0.3989422804014327


def _make_table(key, *, rows: int, features: int):
    """(rows, 1 + features) float32: ReLU'd normals, label in column 0 from
    a planted linear model."""
    kx, kw = jax.random.split(key)
    x = jnp.maximum(jax.random.normal(kx, (rows, features + 1), jnp.float32),
                    0.0)
    w = jax.random.normal(kw, (features + 1,), jnp.float32)
    w = w.at[0].set(0.0) / np.float32(np.sqrt(features))
    y = (x @ w > _RELU_MEAN * jnp.sum(w)).astype(jnp.float32)
    return x.at[:, 0].set(y)


def jax_key(seed: int):
    """A JAX key from any whole-number seed, however large."""
    return jax.random.PRNGKey(
        int(np.random.default_rng(seed).integers(0, 2**31 - 1)))


def make_table(seed: int, rows: int, features: int, sharding):
    fn = jax.jit(partial(_make_table, rows=rows, features=features),
                 out_shardings=sharding)
    return jax.block_until_ready(fn(jax_key(seed)))


def setup(ctx):
    """The table, the estimator and the partition blocks the reference
    reads, for this cell."""
    from repro.core.algorithms.logistic_regression import (
        LogisticRegressionAlgorithm)
    from repro.core.numeric_table import MLNumericTable

    cfg, tr, chips = ctx.config, ctx.traffic, ctx.chips
    features = cfg["features"]
    rows = cfg["rows_per_chip"] * chips
    per_chip = tr["partitions_per_chip"]
    parts = per_chip * chips
    if chips > 1:
        if per_chip != 1:
            raise ValueError("on a mesh the runner holds one partition per "
                             "chip")
        mesh = jax.make_mesh((chips,), ("data",), devices=ctx.devices,
                             axis_types=(jax.sharding.AxisType.Auto,))
        sharding = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("data"))
    else:
        mesh = None
        sharding = jax.sharding.SingleDeviceSharding(ctx.devices[0])
    data = make_table(ctx.seed, rows, features, sharding)
    table = MLNumericTable(data, num_shards=parts, mesh=mesh)
    lr = cfg["lr_times_features"] / features
    algo = LogisticRegressionAlgorithm(
        learning_rate=lr, max_iter=cfg["max_iter"],
        local_batch_size=cfg["minibatch"], schedule=cfg["schedule"])
    if chips > 1:
        shards = sorted(data.addressable_shards, key=lambda s: s.index[0].start)
        blocks = [(s.data, 0, rows // chips) for s in shards]
    else:
        blocks = [(data, p * (rows // parts), rows // parts)
                  for p in range(parts)]
    return table, algo, blocks, lr


def reference(ctx, blocks, lr, dtype="float32") -> np.ndarray:
    cfg = ctx.config
    return fig_a4(blocks, lr, cfg["max_iter"], cfg["minibatch"], dtype)


def rel_err(w, w_ref: np.ndarray) -> float:
    return float(np.max(np.abs(np.asarray(w, np.float64) - w_ref))
                 / np.max(np.abs(w_ref)))


def run(ctx) -> dict:
    cfg = ctx.config
    table, algo, blocks, lr = setup(ctx)
    ctx.mark("table made")
    rows = table.num_rows
    jax.block_until_ready(algo.fit(table).weights)   # compile, cache
    ctx.mark("first fit done")

    start = ctx.begin_window()
    ctx.start_trace()
    fits, answers = [], []
    end = start
    while end - start < ctx.seconds:
        with jax.profiler.TraceAnnotation("bench.fit"):
            t0 = time.perf_counter()
            answers.append(jax.block_until_ready(algo.fit(table).weights))
            end = time.perf_counter()
        fits.append((t0, end))
    ctx.stop_trace()
    window = end - start
    took = sorted(e - s for s, e in fits)
    ctx.log(f"fit() seconds: min {took[0]:.4f} median "
            f"{took[len(took) // 2]:.4f} max {took[-1]:.4f}")
    compiles = ctx.compiles_in_window()
    ctx.read_memory_peak()
    del table

    w_ref = reference(ctx, blocks, lr)
    err = max(rel_err(w, w_ref) for w in answers)
    rounds = cfg["max_iter"]
    return {
        "e2e": {"fit_rows_per_s": rows * rounds * len(fits) / window},
        "stats": {"rows": rows, "rounds": rounds,
                  "features": cfg["features"]},
        "checks": {"weights_rel_err": (err, cfg["limits"]["weights_rel_err"])},
        "attempted": len(fits),
        "failed": 0,
        "compiles_in_window": compiles,
    }

"""Paper Fig. A4 (MLI logistic regression by partition-local SGD), written
plainly and apart from the code under test: it imports nothing of the
program.

Every round, each partition (a contiguous block of rows, label in column 0)
runs sequential SGD in minibatches of ``batch`` rows from the current
weights, ``w -= lr * mean_rows(x * (sigmoid(x . w) - y))``; the partition
weights are then averaged.  ``dtype="float32"`` computes every product at
``HIGHEST`` precision (a float32 matmul on a TPU is otherwise one bf16
pass); ``dtype="bfloat16"`` computes and keeps everything in bfloat16, the
precision below the float32 the configuration states, and serves as the
control that the comparison must refuse.

A block is read one minibatch at a time from the array that holds it, on
the device that holds it, so no copy of the table is made.
"""
from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["fig_a4"]

_PRECISION = {"float32": jax.lax.Precision.HIGHEST,
              "bfloat16": jax.lax.Precision.DEFAULT}


@partial(jax.jit, static_argnames=("rows", "batch", "dtype"))
def _local_sgd(table, start, w, lr, *, rows: int, batch: int, dtype: str):
    dt = jnp.dtype(dtype)
    prec = _PRECISION[dtype]

    def step(w, i):
        mb = jax.lax.dynamic_slice_in_dim(table, start + i * batch, batch)
        mb = mb.astype(dt)
        x, y = mb[:, 1:], mb[:, 0]
        z = jax.nn.sigmoid(jnp.dot(x, w, precision=prec)) - y
        g = jnp.dot(z, x, precision=prec) / jnp.asarray(batch, dt)
        return (w - lr.astype(dt) * g).astype(dt), None

    w, _ = jax.lax.scan(step, w.astype(dt), jnp.arange(rows // batch))
    return w


def fig_a4(blocks: Sequence[Tuple[jax.Array, int, int]], lr: float,
           rounds: int, batch: int, dtype: str = "float32") -> np.ndarray:
    """Weights after ``rounds`` rounds over the partitions ``blocks``, each
    ``(array, first_row, rows)``: the partition is ``array[first_row:
    first_row + rows]``, read where ``array`` lives.  Returns float64."""
    features = blocks[0][0].shape[1] - 1
    home = next(iter(blocks[0][0].devices()))
    w = jax.device_put(jnp.zeros((features,), dtype), home)
    for _ in range(rounds):
        parts = []
        for array, first, rows in blocks:
            dev = next(iter(array.devices()))
            parts.append(jax.device_put(_local_sgd(
                array, jnp.int32(first), jax.device_put(w, dev),
                jnp.float32(lr), rows=rows, batch=batch, dtype=dtype), home))
        w = (sum(p.astype(jnp.float32) for p in parts)
             / len(parts)).astype(dtype)
    return np.asarray(w, np.float64)

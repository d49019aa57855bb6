"""Device-tier MLNumericTable (paper §III-A).

An MLNumericTable is the all-numeric table most algorithms consume: each row
is one feature vector.  Here it is a 2-D ``jnp`` array partitioned row-wise.
Two execution modes:

  * **mesh mode** — the array is placed with a ``NamedSharding`` over the mesh
    ``data`` axis and ``matrixBatchMap`` runs the partition function through
    ``shard_map``: each device sees its block as a :class:`LocalMatrix`,
    exactly the paper's "batch operation on a partition".
  * **emulated mode** (no mesh, e.g. unit tests on one CPU device) — the array
    is split into ``num_shards`` logical partitions and the partition function
    is applied per block inside one jit trace.  Semantics are identical; this
    mirrors running the Spark implementation with `local[n]`.

Global combination is *explicit* (reduce / matrixBatchMap + reduce), keeping
the paper's shared-nothing principle: no hidden distributed linalg.
"""
from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import partition as pt
from repro.core.local_matrix import LocalMatrix

__all__ = ["MLNumericTable"]


def _tree_fold_rows(rows: jnp.ndarray, fn: Callable, identity: jnp.ndarray) -> jnp.ndarray:
    """Log-depth tree reduction of (n, d) rows with an associative,
    commutative ``fn((d,), (d,)) -> (d,)`` — the device-tier analogue of the
    paper's ``reduce``."""
    n = rows.shape[0]
    if n == 0:
        return identity
    pow2 = 1 << (n - 1).bit_length()
    if pow2 != n:
        pad = jnp.broadcast_to(identity, (pow2 - n,) + rows.shape[1:])
        rows = jnp.concatenate([rows, pad], axis=0)
    while rows.shape[0] > 1:
        half = rows.shape[0] // 2
        rows = jax.vmap(fn)(rows[:half], rows[half:])
    return rows[0]


class MLNumericTable:
    """Row-partitioned numeric table; the input type of MLI algorithms."""

    DATA_AXIS = "data"

    def __init__(
        self,
        data: jnp.ndarray,
        num_shards: int,
        mesh: Optional[Mesh] = None,
        names: Optional[Sequence[Optional[str]]] = None,
        data_axes: Optional[Tuple[str, ...]] = None,
    ) -> None:
        if data.ndim != 2:
            raise ValueError("MLNumericTable holds a 2-D (rows, features) array")
        self.mesh = mesh
        self.names = tuple(names) if names is not None else None
        if mesh is not None:
            if data_axes is None:
                data_axes = pt.infer_data_axes(mesh)
            self.data_axes: Tuple[str, ...] = data_axes
            num_shards = pt.num_data_shards(mesh, self.data_axes)
            pt.check_rows_divisible(
                data.shape[0], num_shards,
                what=f"devices on axes {self.data_axes}")
            data = pt.place_rows(data, mesh, self.data_axes)
        else:
            self.data_axes = ()
            pt.check_rows_divisible(data.shape[0], num_shards)
        self.data = data
        self.num_shards = int(num_shards)

    # ------------------------------------------------------------------ #
    # constructors
    # ------------------------------------------------------------------ #
    @classmethod
    def from_numpy(cls, array: np.ndarray, num_shards: Optional[int] = None,
                   mesh: Optional[Mesh] = None,
                   names: Optional[Sequence[Optional[str]]] = None) -> "MLNumericTable":
        # on a mesh the host rows go straight to their shards: staging the
        # whole table on the default device first would cap it at one
        # device's memory
        arr = np.asarray(array) if mesh is not None else jnp.asarray(array)
        if mesh is None and num_shards is None:
            num_shards = 1
        return cls(arr, num_shards=num_shards or 1, mesh=mesh, names=names)

    # ------------------------------------------------------------------ #
    # introspection
    # ------------------------------------------------------------------ #
    @property
    def num_rows(self) -> int:
        return self.data.shape[0]

    @property
    def num_cols(self) -> int:
        return self.data.shape[1]

    numRows, numCols = num_rows, num_cols  # paper spelling

    @property
    def rows_per_shard(self) -> int:
        return self.num_rows // self.num_shards

    def to_numpy(self) -> np.ndarray:
        return np.asarray(self.data)

    def to_local_matrix(self) -> LocalMatrix:
        """Materialize the *whole* table as one LocalMatrix (small tables /
        final factors only — deliberately explicit, per the paper's refusal
        to hide global operations)."""
        return LocalMatrix(self.data)

    toLocalMatrix = to_local_matrix

    @property
    def context(self):  # parity with the paper's ``trainData.context``
        return self

    def broadcast(self, value):
        """Paper's ``ctx.broadcast``: in SPMD the replicated value IS the
        broadcast; returned as-is so reference code reads identically."""
        return value

    # ------------------------------------------------------------------ #
    # row-wise ops (device tier)
    # ------------------------------------------------------------------ #
    def map_rows(self, fn: Callable[[jnp.ndarray], jnp.ndarray]) -> "MLNumericTable":
        out = jax.vmap(fn)(self.data)
        if out.ndim == 1:
            out = out[:, None]
        return MLNumericTable(out, num_shards=self.num_shards, mesh=self.mesh,
                              data_axes=self.data_axes or None)

    def filter_mask(self, pred: Callable[[jnp.ndarray], jnp.ndarray]) -> jnp.ndarray:
        """Static-shape filter: returns the row validity mask (TPU cannot drop
        rows dynamically; downstream ops take the mask)."""
        return jax.vmap(pred)(self.data)

    def reduce(self, fn: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
               identity: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """Combine all rows with an associative+commutative fn (Fig. A1).

        Reduces within each partition, then across partitions — matching the
        distributed execution order."""
        if identity is None:
            identity = jnp.zeros((self.num_cols,), self.data.dtype)

        def shard_reduce(block: jnp.ndarray) -> jnp.ndarray:
            return _tree_fold_rows(block, fn, identity)

        partials = self._per_shard(shard_reduce)          # (num_shards, d)
        return _tree_fold_rows(partials, fn, identity)

    def sum_rows(self) -> jnp.ndarray:
        return jnp.sum(self.data, axis=0)

    def mean_rows(self) -> jnp.ndarray:
        return jnp.mean(self.data, axis=0)

    # ------------------------------------------------------------------ #
    # matrixBatchMap — the heart of the MLI API (Fig. A1)
    # ------------------------------------------------------------------ #
    def matrix_batch_map(
        self,
        fn: Callable[..., LocalMatrix],
        *broadcast_args: Any,
        out_rows_per_shard: Optional[int] = None,
    ) -> "MLNumericTable":
        """Execute ``fn(local_partition, *broadcast_args)`` on every partition
        and concatenate the output matrices row-wise into a new table.

        ``broadcast_args`` are replicated to every partition (the paper's
        driver-side broadcast).  ``fn`` receives a LocalMatrix and must return
        a LocalMatrix (or array) with a fixed number of rows per shard.
        """
        def block_fn(block: jnp.ndarray, *args: Any) -> jnp.ndarray:
            out = fn(LocalMatrix(block), *args)
            out = out.data if isinstance(out, LocalMatrix) else jnp.asarray(out)
            if out.ndim == 1:
                out = out[:, None]
            return out

        stacked = self._per_shard(block_fn, *broadcast_args)  # (shards, r, c)
        flat = stacked.reshape((-1, stacked.shape[-1]))
        if self.mesh is not None:
            flat = pt.place_rows(flat, self.mesh, self.data_axes)
        return MLNumericTable(flat, num_shards=self.num_shards, mesh=self.mesh,
                              data_axes=self.data_axes or None)

    matrixBatchMap = matrix_batch_map  # paper spelling

    # ------------------------------------------------------------------ #
    # execution engine
    # ------------------------------------------------------------------ #
    def _per_shard(self, block_fn: Callable, *broadcast_args: Any) -> jnp.ndarray:
        """Run ``block_fn`` on every partition; return stacked results
        (num_shards, ...).  Execution is delegated to the shared
        :class:`repro.core.runner.DistributedRunner` engine (shard_map when
        a mesh is attached, one partition after another otherwise)."""
        from repro.core.runner import DistributedRunner

        runner = DistributedRunner.for_table(self)
        return runner.partition_apply(self.data, block_fn, broadcast_args)

    def __repr__(self) -> str:  # pragma: no cover
        where = f"mesh{tuple(self.mesh.shape.items())}" if self.mesh is not None else "local"
        return (f"MLNumericTable(rows={self.num_rows}, cols={self.num_cols}, "
                f"shards={self.num_shards}, {where})")

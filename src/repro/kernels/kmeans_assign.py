"""Fused pairwise-distance k-means assignment as a Pallas TPU kernel.

The Lloyd assignment hot path (``core/algorithms/kmeans._local_stats``)
computes, for every row x and centroid c, ``argmin_c ||x − c||²``.  The
naive jnp form materializes the full (rows, k, d) difference tensor (or at
best the (rows, k) distance matrix after an (n,k,d) broadcast) in HBM.
The kernel streams X once and never leaves VMEM:

    ||x − c||² = ||x||² − 2·x·c + ||c||²   and   ||x||² is constant per row,

so the argmin needs only the (rows, k) relative score ``||c||² − 2·x·c``:
one MXU matmul per (row-block × feature-block) tile, accumulated in fp32
scratch across the feature grid axis, with the centroid-norm add and the
argmin fused into the final feature step — the (rows, k) scores never
round-trip HBM.  Same two-pass discipline and block shapes as the logreg
gradient kernel next door (256×512 tiles: X tile 256·512·4B = 512KB fp32
in VMEM, scores 256·k trivially small for practical k).

Centroids ride along transposed, (d, k), so the matmul contracts the
feature-block axis directly; their norms are precomputed once by the
wrapper (O(k·d), negligible next to the O(n·d·k) assignment).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kmeans_assign_pallas"]


def _assign_kernel(x_ref, ct_ref, cn_ref, out_ref, acc_ref):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)        # (BR, BC)
    ct = ct_ref[...].astype(jnp.float32)      # (BC, k)
    acc_ref[...] += jax.lax.dot_general(
        x, ct, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ci == nc - 1)
    def _epilogue():
        score = cn_ref[...] - 2.0 * acc_ref[...]          # (BR, k)
        best = jnp.min(score, axis=1, keepdims=True)
        # first index attaining the min (ties → lowest index, matching
        # jnp.argmin); TPU needs ≥2-D iota, hence broadcasted_iota
        idx = jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
        k = score.shape[1]
        out_ref[...] = jnp.min(jnp.where(score <= best, idx, k),
                               axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols",
                                             "interpret"))
def kmeans_assign_pallas(X, C, *, block_rows=256, block_cols=512,
                        interpret=False):
    """Nearest-centroid assignment.  X: (n, d), C: (k, d) → (n,) int32."""
    n, d = X.shape
    k = C.shape[0]
    br = min(block_rows, n)
    bc = min(block_cols, d)
    if n % br or d % bc:
        raise ValueError(f"(n,d)=({n},{d}) must divide blocks ({br},{bc})")
    ct = C.T.astype(jnp.float32)                           # (d, k)
    cn = jnp.sum(ct * ct, axis=0, keepdims=True)           # (1, k)
    out = pl.pallas_call(
        _assign_kernel,
        grid=(n // br, d // bc),
        in_specs=[
            pl.BlockSpec((br, bc), lambda ri, ci: (ri, ci)),
            pl.BlockSpec((bc, k), lambda ri, ci: (ci, 0)),
            pl.BlockSpec((1, k), lambda ri, ci: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, 1), lambda ri, ci: (ri, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.int32),
        scratch_shapes=[pltpu.VMEM((br, k), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(X, ct, cn)
    return out[:, 0]

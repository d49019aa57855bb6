"""Fused logistic-regression gradient — the paper's §IV-A inner loop — as
two Pallas TPU kernels.

The gradient ∇f = Xᵀ(σ(Xw) − y) has a true data dependency (the residual z
needs the *full-row* margin before any column of the second pass can start),
so with feature tiling the minimum traffic is two streamed passes over X:

  pass 1 (margin):    z = σ(Xw) − y        grid (row-block, col-block),
                      margin accumulated in the output block across the
                      col-block axis; σ and the label subtraction fused into
                      the final col step — z never round-trips HBM unscaled.
  pass 2 (gradient):  g = Xᵀz              grid (col-block, row-block),
                      accumulated across the row-block axis.

A naive jnp implementation materializes the margin and residual in HBM and
reads X twice anyway — the kernels win by (a) fusing σ/subtract into the
matmul epilogue and (b) fp32 accumulation with bf16 streaming of X, halving
the X bytes for the paper's 160K-feature regime (the memory-bound term; see
EXPERIMENTS.md §Perf).

Block shapes default to (256 rows × 512 features): X tile 256·512·2B = 256KB
in VMEM, w/z/g tiles trivially small — comfortably inside the ~16MB VMEM
budget with double buffering, and both matmul dims are multiples of the
128-lane MXU tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["logreg_margin", "logreg_xt_z", "logreg_grad_pallas"]


def _margin_kernel(x_ref, w_ref, y_ref, z_ref, acc_ref):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)       # (BR, BC)
    w = w_ref[...].astype(jnp.float32)       # (BC, 1)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ci == nc - 1)
    def _epilogue():
        y = y_ref[...].astype(jnp.float32)   # (BR, 1)
        z_ref[...] = (jax.nn.sigmoid(acc_ref[...]) - y).astype(z_ref.dtype)


def _xtz_kernel(x_ref, z_ref, g_ref, acc_ref):
    ri = pl.program_id(1)
    nr = pl.num_programs(1)

    @pl.when(ri == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)       # (BR, BC)
    z = z_ref[...].astype(jnp.float32)       # (BR, 1)
    acc_ref[...] += jax.lax.dot_general(
        x, z, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ri == nr - 1)
    def _write():
        g_ref[...] = acc_ref[...].astype(g_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def logreg_margin(X, y, w, *, block_rows=256, block_cols=512, interpret=False):
    """z = σ(Xw) − y.  X: (n, d), y: (n,), w: (d,) → z: (n,) fp32."""
    n, d = X.shape
    br = min(block_rows, n)
    bc = min(block_cols, d)
    if n % br or d % bc:
        raise ValueError(f"(n,d)=({n},{d}) must divide blocks ({br},{bc})")
    z = pl.pallas_call(
        _margin_kernel,
        grid=(n // br, d // bc),
        in_specs=[
            pl.BlockSpec((br, bc), lambda ri, ci: (ri, ci)),
            pl.BlockSpec((bc, 1), lambda ri, ci: (ci, 0)),
            pl.BlockSpec((br, 1), lambda ri, ci: (ri, 0)),
        ],
        out_specs=pl.BlockSpec((br, 1), lambda ri, ci: (ri, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((br, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(X, w.reshape(d, 1), y.reshape(n, 1))
    return z[:, 0]


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def logreg_xt_z(X, z, *, block_rows=256, block_cols=512, interpret=False):
    """g = Xᵀz.  X: (n, d), z: (n,) → g: (d,) fp32."""
    n, d = X.shape
    br = min(block_rows, n)
    bc = min(block_cols, d)
    if n % br or d % bc:
        raise ValueError(f"(n,d)=({n},{d}) must divide blocks ({br},{bc})")
    g = pl.pallas_call(
        _xtz_kernel,
        grid=(d // bc, n // br),
        in_specs=[
            pl.BlockSpec((br, bc), lambda ci, ri: (ri, ci)),
            pl.BlockSpec((br, 1), lambda ci, ri: (ri, 0)),
        ],
        out_specs=pl.BlockSpec((bc, 1), lambda ci, ri: (ci, 0)),
        out_shape=jax.ShapeDtypeStruct((d, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bc, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(X, z.reshape(n, 1))
    return g[:, 0]


def logreg_grad_pallas(X, y, w, *, block_rows=256, block_cols=512,
                       interpret=False):
    """Full fused gradient: ∇f = Xᵀ(σ(Xw) − y), fp32, cast to w.dtype."""
    z = logreg_margin(X, y, w, block_rows=block_rows, block_cols=block_cols,
                      interpret=interpret)
    g = logreg_xt_z(X, z, block_rows=block_rows, block_cols=block_cols,
                    interpret=interpret)
    return g.astype(w.dtype)

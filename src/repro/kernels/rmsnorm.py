"""RMSNorm as a Pallas TPU kernel: one HBM read of x, fp32 statistics.

Grid walks row blocks; each step holds a (block_rows, d) tile plus the (d,)
weight in VMEM.  The unfused jnp version reads x twice (once for the mean of
squares, once for the scale-multiply) when XLA fails to fuse across the
reduction; the kernel guarantees the single pass.  Default tile:
64 rows × d ≤ 8192 → 2MB fp32, well under VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rmsnorm_pallas"]


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)          # (BR, d)
    w = w_ref[...].astype(jnp.float32)          # (1, d)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * w).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_pallas(x: jnp.ndarray, weight: jnp.ndarray, *, eps: float = 1e-6,
                   block_rows: int = 64, interpret: bool = False) -> jnp.ndarray:
    """x: (..., d), weight: (d,).  Returns same shape/dtype as x."""
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    br = min(block_rows, rows)
    if rows % br:
        br = 1  # always divides
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda ri: (ri, 0)),
            pl.BlockSpec((1, d), lambda ri: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda ri: (ri, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2, weight.reshape(1, d))
    return out.reshape(orig_shape)

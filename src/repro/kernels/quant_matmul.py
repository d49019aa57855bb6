"""Quantized int8 matmul as a Pallas TPU kernel — the serving fast path.

The decode hot loop is a stack of skinny matmuls (activations (B·S, K)
against projection weights (K, N)).  At serving time the weights are
static, so they ride quantized: symmetric per-output-channel int8
(``models/layers/quant.quantize_weight``), and the activation rows are
quantized on the fly (``ops.quantize_rows``).  The kernel computes

    out = (xq · wq) * x_scale[:, None] * w_scale[None, :]

with the product accumulated on the MXU in **int32** — integer addition is
exact whatever the K-grid order, so the kernel is *bitwise* equal to
``ref.quant_matmul_ref`` (not merely close), and the fp32 epilogue applies
both scale vectors in the oracle's operand order.  int8 operands draw
half the HBM bandwidth of bf16 and a quarter of fp32 — on decode shapes
(M small, K·N the traffic) the weight stream IS the roofline, which is
the whole point of the quantized path.

Same two-pass discipline and accumulator layout as ``kmeans_assign`` /
``logreg_grad`` next door: 3-D grid (rows, cols, k-blocks) with the
k-axis innermost ("arbitrary"), an (BM, BN) int32 VMEM scratch
accumulator initialized at the first k-step, and the dequantizing
epilogue fused into the last k-step so the int32 partials never
round-trip HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["quant_matmul_pallas"]


def _qmm_kernel(xq_ref, wq_ref, xs_ref, ws_ref, out_ref, acc_ref):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        xq_ref[...], wq_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(ki == nk - 1)
    def _epilogue():
        out_ref[...] = (acc_ref[...].astype(jnp.float32)
                        * xs_ref[...] * ws_ref[...])


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def quant_matmul_pallas(xq, xs, wq, ws, *, block_m=256, block_n=256,
                        block_k=512, interpret=False):
    """Quantized matmul.  xq: (M, K) int8, xs: (M,) fp32 row scales,
    wq: (K, N) int8, ws: (N,) fp32 column scales → (M, N) fp32."""
    M, K = xq.shape
    K2, N = wq.shape
    if K != K2 or xs.shape != (M,) or ws.shape != (N,):
        raise ValueError(f"shape mismatch: xq{xq.shape} wq{wq.shape} "
                         f"xs{xs.shape} ws{ws.shape}")
    bm = min(block_m, M)
    bn = min(block_n, N)
    bk = min(block_k, K)
    if M % bm or N % bn or K % bk:
        raise ValueError(f"(M,N,K)=({M},{N},{K}) must divide blocks "
                         f"({bm},{bn},{bk})")
    xs2 = xs.astype(jnp.float32)[:, None]              # (M, 1)
    ws2 = ws.astype(jnp.float32)[None, :]              # (1, N)
    return pl.pallas_call(
        _qmm_kernel,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((bm, 1), lambda mi, ni, ki: (mi, 0)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xq, wq, xs2, ws2)

"""Flash attention as a Pallas TPU kernel (online-softmax, VMEM-tiled).

TPU adaptation of the Flash-Attention-2 schedule: the (Sq, Sk) logits matrix
is never materialized in HBM.  The grid walks (batch·q-head, q-block, k-block)
with the k-block axis innermost; running max/denominator/accumulator live in
VMEM scratch and the output block is written once, on the final k step.  All
matmuls hit the MXU with fp32 accumulation; block shapes are multiples of 128
on the lane dim so the MXU tiles are hardware-aligned.

Mask variants (static Python switches — each compiles to its own kernel):
  causal              k_pos ≤ q_pos
  sliding window W    q_pos − k_pos < W      (mixtral, gemma3, recurrentgemma)
  chunked C           same attention chunk   (llama4 iRoPE)

GQA: the kv-head index for block fetch is derived from the fused (b·H + h)
grid coordinate, so kv tensors stay un-broadcast in HBM (memory term wins vs
jnp.repeat — see EXPERIMENTS.md §Perf).

Validated against ``ref.flash_attention_ref`` in interpret mode (this
container is CPU-only; the TPU is the deployment target).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

NEG_INF = -2.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  chunk: Optional[int], block_q: int, block_k: int,
                  sq: int, sk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # (BQ, hd)
    k = k_ref[0, 0].astype(jnp.float32)               # (BK, hd)
    v = v_ref[0, 0].astype(jnp.float32)               # (BK, hd)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # absolute positions (prefill convention: queries are the last sq of sk)
    pos_q = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
        + (sk - sq)
    pos_k = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), bool)
    if causal:
        mask &= pos_k <= pos_q
    if window is not None:
        mask &= pos_q - pos_k < window
    if chunk is not None:
        mask &= (pos_q // chunk) == (pos_k // chunk)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                            # (BQ, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)     # (BQ, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be exp(0)=1
    s_exp = jnp.where((s > NEG_INF / 2), jnp.exp(s - m_new), 0.0)
    alpha = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_new), 0.0)

    l_new = alpha * l_ref[...] + jnp.sum(s_exp, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        s_exp, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "chunk", "scale", "block_q", "block_k", "interpret"))
def flash_attention(
    q: jnp.ndarray,            # (B, H, Sq, hd)
    k: jnp.ndarray,            # (B, KV, Sk, hd)
    v: jnp.ndarray,            # (B, KV, Sk, hd)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    chunk: Optional[int] = None,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, H, Sq, hd = q.shape
    _, KV, Sk, _ = k.shape
    if H % KV:
        raise ValueError(f"q heads {H} not divisible by kv heads {KV}")
    groups = H // KV
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    if Sq % bq or Sk % bk:
        raise ValueError(f"seq lens ({Sq},{Sk}) must divide blocks ({bq},{bk})")
    scale = hd ** -0.5 if scale is None else scale

    grid = (B * H, Sq // bq, Sk // bk)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window, chunk=chunk,
        block_q=bq, block_k=bk, sq=Sq, sk=Sk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bh, qi, ki: (bh // H, bh % H, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bh, qi, ki: (bh // H, (bh % H) // groups, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bh, qi, ki: (bh // H, (bh % H) // groups, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda bh, qi, ki: (bh // H, bh % H, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom l
            pltpu.VMEM((bq, hd), jnp.float32),  # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(
        q.reshape(B, H, Sq, hd),
        k,
        v,
    )

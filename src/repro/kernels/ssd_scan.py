"""Mamba-2 SSD chunk scan as a Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060 §6): the paper's CUDA
implementation pipelines warp-level scans; on TPU the right decomposition is
the *chunked dual form* — the intra-chunk term is an (L, L) masked matmul
chain that maps straight onto the MXU, and the inter-chunk recurrence is a
sequential grid dimension whose (P, N) state lives in VMEM scratch (exactly
the flash-attention accumulator pattern, with a decaying state instead of a
softmax numerator).

Grid: (B·H, n_chunks), chunk axis innermost/"arbitrary" (sequential on TPU),
so the state never round-trips HBM between chunks.  Per grid step the
working set is L·(P + 2N) + L² + P·N floats — for the defaults (L=64,
P=64, N=128) about 100 KB, far under VMEM with room for double buffering.

Numerics match ``ref.ssd_chunk_scan_ref`` (fp32 throughout; the exponent
clamp keeps masked entries finite before the mask-multiply).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_chunk_scan"]

_EXACT = jax.lax.Precision.HIGHEST


def _ssd_kernel(la_ref, dx_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref,
                h_ref, *, L: int, P: int, N: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    la = la_ref[0, 0, 0].astype(jnp.float32)          # (1, L)
    dx = dx_ref[0, 0, 0].astype(jnp.float32)          # (L, P)
    Bc = b_ref[0, 0].astype(jnp.float32)              # (L, N)
    Cc = c_ref[0, 0].astype(jnp.float32)              # (L, N)
    h = h_ref[...]                                    # (P, N)

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = s_idx <= t_idx
    # inclusive cumsum as a triangular matmul (Mosaic lowers no cumsum),
    # once as a column and once as a row; HIGHEST keeps it f32-exact
    tri = causal.astype(jnp.float32)
    nt = (((1,), (1,)), ((), ()))
    cum_col = jax.lax.dot_general(tri, la, nt, precision=_EXACT,
                                  preferred_element_type=jnp.float32)  # (L, 1)
    cum_row = jax.lax.dot_general(la, tri, nt, precision=_EXACT,
                                  preferred_element_type=jnp.float32)  # (1, L)
    total = jnp.sum(la, axis=1, keepdims=True)        # (1, 1)
    # intra-chunk dual (attention-like) term
    CB = jax.lax.dot_general(Cc, Bc, nt,
                             preferred_element_type=jnp.float32)   # (L, L)
    decay = jnp.exp(jnp.minimum(cum_col - cum_row, 0.0))
    M = jnp.where(causal, CB * decay, 0.0)
    y_intra = jax.lax.dot_general(M, dx, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (L, P)
    # inter-chunk: carried state contribution
    Ch = jax.lax.dot_general(Cc, h, nt,
                             preferred_element_type=jnp.float32)   # (L, P)
    y_inter = jnp.exp(cum_col) * Ch
    y_ref[0, 0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h' = a_chunk·h + Σ_s w_tail(s)·dx(s)⊗B(s)
    wdx = jnp.exp(total - cum_col) * dx               # (L, P)
    h_new = h * jnp.exp(total) + jax.lax.dot_general(
        wdx, Bc, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    h_ref[...] = h_new

    @pl.when(ci == nc - 1)
    def _final():
        hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan(
    log_a: jnp.ndarray,   # (B, H, S)
    dx: jnp.ndarray,      # (B, H, S, P)
    Bm: jnp.ndarray,      # (B, S, N)
    Cm: jnp.ndarray,      # (B, S, N)
    h0: Optional[jnp.ndarray] = None,   # (B, H, P, N)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y (B,H,S,P) fp32, h_final (B,H,P,N) fp32)."""
    B, H, S, P = dx.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    if S % L:
        raise ValueError(f"S={S} must divide chunk {L}")
    C = S // L
    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)

    # a singleton row axis makes the block's last two dims (1, L) equal
    # the array's, which the TPU's (8, 128) tiling rule requires
    la_c = log_a.reshape(B, H, C, 1, L)
    dx_c = dx.reshape(B, H, C, L, P)
    B_c = Bm.reshape(B, C, L, N)
    C_c = Cm.reshape(B, C, L, N)

    kernel = functools.partial(_ssd_kernel, L=L, P=P, N=N)
    y, h_fin = pl.pallas_call(
        kernel,
        grid=(B * H, C),
        in_specs=[
            pl.BlockSpec((1, 1, 1, 1, L),
                         lambda bh, c: (bh // H, bh % H, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, L, P),
                         lambda bh, c: (bh // H, bh % H, c, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda bh, c: (bh // H, c, 0, 0)),
            pl.BlockSpec((1, 1, L, N), lambda bh, c: (bh // H, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bh, c: (bh // H, bh % H, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, L, P),
                         lambda bh, c: (bh // H, bh % H, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bh, c: (bh // H, bh % H, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, C, L, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(la_c, dx_c, B_c, C_c, h0)
    return y.reshape(B, H, S, P), h_fin

"""Public jit'd wrappers for the Pallas kernels.

Each wrapper validates shapes and auto-selects ``interpret=True`` off-TPU.
For shapes the kernels' blocks cannot tile (non-divisible sequence lengths,
the paper's 160,000 features against 512-wide blocks) the wrappers differ by
backend.  Off-TPU they return the pure-jnp oracle, so callers never have to
branch.  On TPU they never leave the kernel path in silence: where zero
padding is exact (the logistic-regression gradient, k-means assignment, the
int8 matmul) they pad to the tile and slice the result; elsewhere (flash
attention, the SSD scan) they raise.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.kmeans_assign import kmeans_assign_pallas
from repro.kernels.logreg_grad import logreg_grad_pallas
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_chunk_scan as _ssd_scan

__all__ = ["flash_attention", "kmeans_assign", "logreg_grad", "quant_matmul",
           "quantize_rows", "rmsnorm", "ssd_chunk_scan", "on_tpu"]


@functools.lru_cache(None)
def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interp() -> bool:
    return not on_tpu()


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    """Zero-pad ``x`` along ``axis`` up to a multiple of ``multiple``."""
    extra = -x.shape[axis] % multiple
    if not extra:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths)


def _untileable_on_tpu(name: str, detail: str) -> None:
    if on_tpu():
        raise ValueError(f"{name}: {detail} does not tile the kernel's blocks "
                         f"and has no exact padding; no reference fallback "
                         f"runs on TPU")


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, chunk: Optional[int] = None,
                    scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128) -> jnp.ndarray:
    """(B, H, Sq, hd) x (B, KV, Sk, hd)² -> (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    _, KV, Sk, _ = k.shape
    if v.shape != k.shape:
        raise ValueError(f"k/v shape mismatch: {k.shape} vs {v.shape}")
    if H % KV:
        raise ValueError(f"H={H} not divisible by KV={KV}")
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    if Sq % bq or Sk % bk:
        _untileable_on_tpu("flash_attention", f"Sq={Sq}, Sk={Sk}")
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       chunk=chunk, scale=scale)
    return _flash(q, k, v, causal=causal, window=window, chunk=chunk,
                  scale=scale, block_q=bq, block_k=bk, interpret=_interp())


def kmeans_assign(X, C, *, block_rows: int = 256,
                  block_cols: int = 512) -> jnp.ndarray:
    """Nearest-centroid assignment argmin_c ||x − c||² (fused pairwise
    distances).  X: (n, d), C: (k, d) → (n,) int32."""
    if X.ndim != 2 or C.ndim != 2 or X.shape[1] != C.shape[1]:
        raise ValueError(f"shape mismatch: X{X.shape} C{C.shape}")
    n, d = X.shape
    br = min(block_rows, n)
    bc = min(block_cols, d)
    if n % br or d % bc:
        if not on_tpu():
            return ref.kmeans_assign_ref(X, C)
        # zero columns add nothing to any distance; padded rows are dropped
        Xp = _pad_to(_pad_to(X, 0, br), 1, bc)
        return kmeans_assign_pallas(Xp, _pad_to(C, 1, bc), block_rows=br,
                                    block_cols=bc, interpret=_interp())[:n]
    return kmeans_assign_pallas(X, C, block_rows=br, block_cols=bc,
                                interpret=_interp())


def logreg_grad(X, y, w, *, block_rows: int = 256, block_cols: int = 512) -> jnp.ndarray:
    """∇f = Xᵀ(σ(Xw) − y) fused.  X: (n,d), y: (n,), w: (d,)."""
    n, d = X.shape
    if y.shape != (n,) or w.shape != (d,):
        raise ValueError(f"shape mismatch: X{X.shape} y{y.shape} w{w.shape}")
    br = min(block_rows, n)
    bc = min(block_cols, d)
    if n % br or d % bc:
        if not on_tpu():
            return ref.logreg_grad_ref(X, y, w)
        # zero rows and columns add nothing to the margins or the gradient
        g = logreg_grad_pallas(_pad_to(_pad_to(X, 0, br), 1, bc),
                               _pad_to(y, 0, br), _pad_to(w, 0, bc),
                               block_rows=br, block_cols=bc, interpret=_interp())
        return g[:d]
    return logreg_grad_pallas(X, y, w, block_rows=br, block_cols=bc,
                              interpret=_interp())


def quantize_rows(x):
    """Symmetric per-row int8 quantization: returns ``(xq, scale)`` with
    ``xq`` int8 of ``x.shape`` and ``scale`` fp32 of ``x.shape[:-1]`` such
    that ``xq * scale[..., None] ≈ x``.  The ``1e-8`` floor keeps all-zero
    rows from dividing by zero (they quantize to zeros with a tiny scale)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0 + 1e-8
    xq = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return xq, scale


def quant_matmul(xq, x_scale, wq, w_scale, *, block_m: int = 256,
                 block_n: int = 256, block_k: int = 512) -> jnp.ndarray:
    """Quantized int8×int8 matmul with fp32 dequantizing epilogue.
    xq: (M, K) int8, x_scale: (M,), wq: (K, N) int8, w_scale: (N,) → (M, N)
    fp32 equal to ``(xq·wq) * x_scale[:,None] * w_scale[None,:]``.

    On TPU this is the Pallas kernel (int32 MXU accumulation, bit-exact vs
    ``ref.quant_matmul_ref``), with shapes that do not tile zero-padded
    (zero rows of ``wq`` add nothing to the int32 sums).  Everywhere else —
    including the CPU serving path — the dequantized product is taken in
    fp32, which is mathematically the same sum and exact as long as every
    int32 partial fits an fp32 mantissa (K·127² < 2²⁴, i.e. K ≲ 1000; true
    for every config in this repo).  We do NOT run the interpret-mode kernel
    here: per-element Pallas interpretation is orders of magnitude too slow
    for a decode hot loop (same policy as ``use_flash_kernel`` off-TPU)."""
    M, K = xq.shape
    K2, N = wq.shape
    if K != K2 or x_scale.shape != (M,) or w_scale.shape != (N,):
        raise ValueError(f"shape mismatch: xq{xq.shape} wq{wq.shape} "
                         f"x_scale{x_scale.shape} w_scale{w_scale.shape}")
    bm = min(block_m, M)
    bn = min(block_n, N)
    bk = min(block_k, K)
    if on_tpu():
        out = quant_matmul_pallas(
            _pad_to(_pad_to(xq, 0, bm), 1, bk), _pad_to(x_scale, 0, bm),
            _pad_to(_pad_to(wq, 0, bk), 1, bn), _pad_to(w_scale, 0, bn),
            block_m=bm, block_n=bn, block_k=bk, interpret=_interp())
        return out[:M, :N]
    acc = jnp.dot(xq.astype(jnp.float32), wq.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    return (acc * x_scale.astype(jnp.float32)[:, None]
            * w_scale.astype(jnp.float32)[None, :])


def rmsnorm(x, weight, *, eps: float = 1e-6, block_rows: int = 64) -> jnp.ndarray:
    """RMSNorm over the last dim.  x: (..., d), weight: (d,)."""
    if weight.shape != (x.shape[-1],):
        raise ValueError(f"weight {weight.shape} vs x feature dim {x.shape[-1]}")
    return rmsnorm_pallas(x, weight, eps=eps, block_rows=block_rows,
                          interpret=_interp())


def ssd_chunk_scan(log_a, dx, Bm, Cm, h0=None, *, chunk: int = 64):
    """Mamba-2 SSD chunked scan.  log_a: (B,H,S), dx: (B,H,S,P),
    Bm/Cm: (B,S,N) → (y (B,H,S,P), h_final (B,H,P,N))."""
    B, H, S, P = dx.shape
    if log_a.shape != (B, H, S) or Bm.shape[:2] != (B, S):
        raise ValueError(f"shape mismatch: log_a{log_a.shape} dx{dx.shape} "
                         f"Bm{Bm.shape}")
    if S % min(chunk, S):
        _untileable_on_tpu("ssd_chunk_scan", f"S={S} with chunk={chunk}")
        return ref.ssd_chunk_scan_ref(log_a, dx, Bm, Cm, h0, chunk=S)
    return _ssd_scan(log_a, dx, Bm, Cm, h0, chunk=chunk, interpret=_interp())

"""Mamba-2 SSD block (state-space duality, arXiv:2405.21060).

The SSD layer computes a selective state-space model

    h_t = a_t ⊙ h_{t−1} + (Δ_t x_t) ⊗ B_t          a_t = exp(Δ_t · A),  A < 0
    y_t = C_t · h_t + D ⊙ x_t

with scalar-per-head decay (the "SSD" restriction), multi-head over the
expanded inner width (P = head dim, N = state dim).  Training/prefill uses
the paper's *chunked dual form*: within a chunk of length L the output is an
attention-like matmul ``M = (C Bᵀ) ⊙ decay`` (the "duality"); across chunks a
single recurrent state is carried by a ``lax.scan``.  This is the TPU-native
adaptation: the intra-chunk quadratic form maps onto the MXU, the inter-chunk
scan is O(S/L) sequential steps — no CUDA-style warp-level scan needed.

Decode is the O(1) recurrence.

As in Mamba-2, the causal depthwise convolution runs over ``xBC``, the
concatenation of x (``d_inner`` channels), B and C (``ssm_state`` each), and
B and C are split off after the convolution and its SiLU.  The in-projection
is kept as ``wz`` / ``w_xbc`` / ``wdt`` instead of mamba2's one packed
in_proj, so that each output shards on its own axis; the math is identical.

Right-padded ragged prefill (``lengths``): at a pad position Δ is 0, so
``a = exp(0) = 1`` and ``Δx = 0`` leave the state ``h`` exactly as the last
real position left it, and each row's conv taps are taken from its own last
``conv_width - 1`` real positions.  A row's state after a padded prefill is
its unpadded prefill's.  The same rule pads a prefill longer than one chunk
to whole chunks, so the dual form's (L, L) blocks stay ``ssd_chunk`` wide.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers.quant import linear_or_quant
from repro.models.params import bias as bias_init
from repro.models.params import linear, ones_vec, split_tree_of

__all__ = ["ssd_init", "ssd_apply", "init_ssd_cache"]


def _conv_channels(cfg: ArchConfig) -> int:
    """x, B and C: the channels the causal convolution runs over."""
    return cfg.d_inner_ssd + 2 * cfg.ssm_state


def ssd_init(key: jax.Array, cfg: ArchConfig, dtype):
    d = cfg.d_model
    di = cfg.d_inner_ssd
    h = cfg.ssd_heads
    k = cfg.conv_width
    c = _conv_channels(cfg)
    ks = jax.random.split(key, 8)
    a0 = jax.random.uniform(ks[0], (h,), jnp.float32, 1.0, 16.0)
    mixed = {
        "wz": linear(ks[1], (d, di), ("embed", "rnn"), fan_in=d, dtype=dtype),
        "w_xbc": linear(ks[2], (d, c), ("embed", "rnn"), fan_in=d, dtype=dtype),
        "wdt": linear(ks[5], (d, h), ("embed", "ssd_heads"), fan_in=d, dtype=dtype),
        "dt_bias": bias_init((h,), ("ssd_heads",), jnp.float32),
        "A_log": (jnp.log(a0), ("ssd_heads",)),
        "D": (jnp.ones((h,), jnp.float32), ("ssd_heads",)),
        "conv_w": linear(ks[6], (k, c), (None, "rnn"), fan_in=k, dtype=dtype),
        "conv_b": bias_init((c,), ("rnn",), dtype),
        "norm_scale": ones_vec((di,), ("rnn",), dtype),
        "w_out": linear(ks[7], (di, d), ("rnn", "embed"), fan_in=di, dtype=dtype),
    }
    return split_tree_of(mixed)


def init_ssd_cache(cfg: ArchConfig, batch: int, dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    return {
        "h": jnp.zeros((batch, cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssm_state), jnp.float32),
        "conv": jnp.zeros((batch, cfg.conv_width - 1, _conv_channels(cfg)), dtype),
    }


def _causal_conv(x, w, b, state, lengths=None):
    """Depthwise causal conv + SiLU over (B, S, C).  Returns the output and
    the taps a later call continues from: the last ``K - 1`` inputs, or with
    ``lengths`` (B,) each row's last ``K - 1`` *real* inputs (columns
    ``lengths[b] - K + 1 .. lengths[b] - 1``, reaching into ``state`` for a
    row shorter than the taps)."""
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i : i + x.shape[1]] * w[i] for i in range(K)) + b
    if lengths is None:
        taps = xp[:, -(K - 1):]
    else:
        # xp column j holds input j - (K - 1); a row's last real input is
        # column lengths + K - 2
        idx = jnp.asarray(lengths, jnp.int32)[:, None] + jnp.arange(K - 1)
        taps = jnp.take_along_axis(xp, idx[:, :, None], axis=1)
    return jax.nn.silu(y.astype(jnp.float32)).astype(x.dtype), taps


def _gated_norm(y: jnp.ndarray, z: jnp.ndarray, scale: jnp.ndarray,
                eps: float) -> jnp.ndarray:
    g = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
    var = jnp.mean(g * g, axis=-1, keepdims=True)
    return (g * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(y.dtype)


def _out(params, y, z, cfg: ArchConfig, dtype):
    out = _gated_norm(y.astype(dtype), z, params["norm_scale"], cfg.norm_eps)
    return linear_or_quant(out, params["w_out"], "bse,ed->bsd",
                           preferred_element_type=jnp.float32).astype(dtype)


def ssd_apply(params: Dict[str, jnp.ndarray], x: jnp.ndarray, *,
              cfg: ArchConfig, mode: str,
              cache: Optional[Dict[str, jnp.ndarray]] = None,
              lengths: Optional[jnp.ndarray] = None,
              ) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """``lengths`` (B,): right-padded ragged prefill, row b's real tokens in
    columns 0..lengths[b]-1 (see the module docstring)."""
    Bsz, S, D = x.shape
    H, P, N = cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssm_state
    di = cfg.d_inner_ssd

    def proj(name):
        return linear_or_quant(x, params[name], "bsd,de->bse",
                               preferred_element_type=jnp.float32)

    z = proj("wz").astype(x.dtype)
    xbc = proj("w_xbc").astype(x.dtype)
    conv_state = cache["conv"] if cache is not None else None
    xbc, new_conv = _causal_conv(xbc, params["conv_w"], params["conv_b"],
                                 conv_state, lengths)
    xin = xbc[..., :di]
    Bmat = xbc[..., di:di + N].astype(jnp.float32)
    Cmat = xbc[..., di + N:].astype(jnp.float32)
    dt = jax.nn.softplus(proj("wdt").astype(jnp.float32)
                         + params["dt_bias"])                  # (B,S,H) fp32
    if lengths is not None:
        real = jnp.arange(S)[None, :] < jnp.asarray(lengths, jnp.int32)[:, None]
        dt = jnp.where(real[..., None], dt, 0.0)               # pad: a=1, Δx=0
    A = -jnp.exp(params["A_log"])                              # (H,) negative
    log_a = dt * A                                             # (B,S,H) ≤ 0

    xh = xin.reshape(Bsz, S, H, P).astype(jnp.float32)
    dtx = dt[..., None] * xh                                   # (B,S,H,P)

    if mode == "decode":
        assert cache is not None
        a = jnp.exp(log_a[:, 0])                               # (B,H)
        h_new = cache["h"] * a[..., None, None] + jnp.einsum(
            "bhp,bn->bhpn", dtx[:, 0], Bmat[:, 0])
        y = jnp.einsum("bhpn,bn->bhp", h_new, Cmat[:, 0])
        y = y + params["D"][None, :, None] * xh[:, 0]
        return (_out(params, y.reshape(Bsz, 1, H * P), z, cfg, x.dtype),
                {"h": h_new, "conv": new_conv})

    # ---------------- chunked dual form ---------------- #
    L = min(cfg.ssd_chunk, S)
    n_chunks = -(-S // L)
    pad = n_chunks * L - S          # zero log_a and Δx: the state passes

    def to_chunks(t):
        t = jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
        return t.reshape((Bsz, n_chunks, L) + t.shape[2:])

    log_a_c = to_chunks(log_a)       # (B,c,L,H)
    dtx_c = to_chunks(dtx)           # (B,c,L,H,P)
    B_c = to_chunks(Bmat)            # (B,c,L,N)
    C_c = to_chunks(Cmat)            # (B,c,L,N)

    h0 = cache["h"] if cache is not None else jnp.zeros((Bsz, H, P, N), jnp.float32)

    causal = jnp.tril(jnp.ones((L, L), bool))

    def chunk_step(h, inputs):
        la, dx, Bc, Cc = inputs                       # (B,L,H) (B,L,H,P) (B,L,N) (B,L,N)
        cum = jnp.cumsum(la, axis=1)                  # inclusive (B,L,H)
        # intra-chunk dual (attention-like) term
        CB = jnp.einsum("btn,bsn->bts", Cc, Bc)       # (B,L,L)
        # clamp the (masked-out) s > t entries before exp — they would
        # overflow to inf and poison the mask-multiply with inf*0=NaN.
        # For s ≤ t the exponent is ≤ 0, so the clamp is exact.
        decay = jnp.exp(jnp.minimum(cum[:, :, None, :] - cum[:, None, :, :], 0.0))
        M = CB[..., None] * decay * causal[None, :, :, None]
        y_intra = jnp.einsum("btsh,bshp->bthp", M, dx)
        # inter-chunk: contribution of carried state
        y_inter = jnp.exp(cum)[..., None] * jnp.einsum("btn,bhpn->bthp", Cc, h)
        # state update
        w_tail = jnp.exp(cum[:, -1:, :] - cum)        # (B,L,H)
        h_new = h * jnp.exp(cum[:, -1])[..., None, None] + jnp.einsum(
            "bsh,bshp,bsn->bhpn", w_tail, dx, Bc)
        return h_new, y_intra + y_inter

    h_fin, y = jax.lax.scan(chunk_step, h0,
                            (jnp.moveaxis(log_a_c, 1, 0),
                             jnp.moveaxis(dtx_c, 1, 0),
                             jnp.moveaxis(B_c, 1, 0),
                             jnp.moveaxis(C_c, 1, 0)))
    y = jnp.moveaxis(y, 0, 1).reshape(Bsz, n_chunks * L, H, P)[:, :S]
    y = y + params["D"][None, None, :, None] * xh
    out = _out(params, y.reshape(Bsz, S, H * P), z, cfg, x.dtype)
    new_cache = {"h": h_fin, "conv": new_conv} if cache is not None else None
    return out, new_cache

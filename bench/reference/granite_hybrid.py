"""Plain Granite-4.0-H forward pass in float32: Mamba-2 layers and NoPE
GQA attention layers, each followed by a SwiGLU MLP (the Hugging Face
``GraniteMoeHybridForCausalLM`` equations with no experts; the Mamba-2
mixer of arXiv:2405.21060), written apart from the code under test: it
imports nothing of the program.

    x = embed[tokens] * embedding_multiplier
    per layer, by ``layer_types``:
      mamba:     h = RMSNorm(x) * ln1
                 z = h Wz;  xBC = h Wxbc;  dt = softplus(h Wdt + dt_bias)
                 xBC = silu(causal depthwise conv of xBC, width d_conv, + b)
                 x_t, B_t, C_t = split(xBC_t)           (one group: B, C (N,))
                 s_t = exp(dt_t A) s_{t-1} + dt_t x_t (x) B_t    A = -exp(A_log)
                 y_t = C_t . s_t + D x_t                 (per head: s (P, N))
                 x = x + residual_multiplier * (RMSNorm(y * silu(z)) * norm) Wout
      attention: h = RMSNorm(x) * ln1;  q, k, v = h Wq, h Wk, h Wv (no bias)
                 x = x + residual_multiplier *
                     softmax(q k^T * attention_multiplier + causal) v Wo
                 (no positional encoding; GQA: kv heads < heads)
      then:      x = x + residual_multiplier *
                     (silu(RMSNorm(x) * ln2 Wg) * (RMSNorm(x) * ln2 Wu)) Wd
    logits = RMSNorm(x) * final_norm  embed^T / logits_scaling

The state space part is the plain recurrence, one position after another
in a ``lax.scan``, not the chunked dual form the program runs.  Every
product runs at ``HIGHEST`` precision under
``jax.default_matmul_precision("highest")``, since a float32 matmul on a
TPU is otherwise one bf16 pass.  One sequence at a time, one layer at a
time, each layer's weights handed over in float32 as it runs, so that it
fits beside the served bf16 weights; every sequence is right-padded to the
same ``length`` (everything is causal, so padding changes nothing before
it), so that one program of each kind serves them all.

Departures from the published description: none in the equations.  The
published in-projection is one packed matrix [z, xBC, dt]; here it comes as
its three parts, which is the same product.  The gated norm is one group
over all of ``d_inner`` (n_groups 1).

Weights come as plain float32 arrays in this layout.  A mamba layer:
``ln1 (D,)``, ``in_z (D, di)``, ``in_xbc (D, di + 2N)``, ``in_dt (D, H)``,
``conv_w (d_conv, di + 2N)`` (tap d_conv - 1 multiplies the current
position), ``conv_b (di + 2N,)``, ``dt_bias (H,)``, ``A_log (H,)``,
``D (H,)``, ``norm (di,)``, ``out (di, D)``.  An attention layer: ``ln1``,
``q_w (D, heads * hd)``, ``k_w (D, kv * hd)``, ``v_w``, ``o_w (heads * hd,
D)``.  Both: ``ln2 (D,)``, ``gate (D, F)``, ``up (D, F)``, ``down (F, D)``.
And ``embed (V, D)``, ``final_norm (D,)``.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["hidden", "logit_rows"]

_HI = jax.lax.Precision.HIGHEST
_dot = partial(jnp.matmul, precision=_HI)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _mlp(x, w, eps, mult):
    h = _rmsnorm(x, w["ln2"], eps)
    return x + mult * _dot(jax.nn.silu(_dot(h, w["gate"])) * _dot(h, w["up"]),
                           w["down"])


@partial(jax.jit, static_argnames=("heads", "head_dim", "state", "eps",
                                   "mult"))
def _mamba_layer(x, w, *, heads: int, head_dim: int, state: int, eps: float,
                 mult: float):
    S = x.shape[0]
    di = heads * head_dim
    h = _rmsnorm(x, w["ln1"], eps)
    z = _dot(h, w["in_z"])
    xbc = _dot(h, w["in_xbc"])
    dt = jax.nn.softplus(_dot(h, w["in_dt"]) + w["dt_bias"])        # (S, H)
    K = w["conv_w"].shape[0]
    xp = jnp.pad(xbc, ((K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xp[k:k + S] * w["conv_w"][k] for k in range(K))
                      + w["conv_b"])
    xs = xbc[:, :di].reshape(S, heads, head_dim)
    Bm, Cm = xbc[:, di:di + state], xbc[:, di + state:]
    A = -jnp.exp(w["A_log"])                                        # (H,)

    def step(s, inp):
        x_t, B_t, C_t, dt_t = inp
        s = (jnp.exp(dt_t * A)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :])
        y = jnp.einsum("hpn,n->hp", s, C_t, precision=_HI) + w["D"][:, None] * x_t
        return s, y

    s0 = jnp.zeros((heads, head_dim, state), jnp.float32)
    _, y = jax.lax.scan(step, s0, (xs, Bm, Cm, dt))
    g = y.reshape(S, di) * jax.nn.silu(z)
    g = _rmsnorm(g, w["norm"], eps)
    x = x + mult * _dot(g, w["out"])
    return _mlp(x, w, eps, mult)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "mult",
                                   "scale"))
def _attention_layer(x, w, *, heads: int, kv_heads: int, eps: float,
                     mult: float, scale: float):
    S = x.shape[0]
    hd = w["q_w"].shape[1] // heads
    h = _rmsnorm(x, w["ln1"], eps)
    q = _dot(h, w["q_w"]).reshape(S, heads, hd)
    k = _dot(h, w["k_w"]).reshape(S, kv_heads, hd)
    v = _dot(h, w["v_w"]).reshape(S, kv_heads, hd)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) * scale
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=_HI).reshape(S, heads * hd)
    x = x + mult * _dot(o, w["o_w"])
    return _mlp(x, w, eps, mult)


@partial(jax.jit, static_argnames=("mult",))
def _embed(embed, tokens, *, mult: float):
    return jnp.take(embed, tokens, axis=0) * mult


@partial(jax.jit, static_argnames=("eps",))
def _final(x, w, *, eps: float):
    return _rmsnorm(x, w, eps)


def hidden(cfg: Dict, layer: Callable[[int], Dict], embed, final_norm,
           tokens: np.ndarray, length: int) -> jax.Array:
    """Final-norm hidden states (length, D) of one token sequence of at
    most ``length`` tokens; rows past the sequence are padding.  ``cfg`` is
    the configuration file's dict (Hugging Face key names); ``layer(i)``
    gives layer i's weights in the layout above, in float32."""
    if cfg["position_embedding_type"] != "nope":
        raise ValueError("this reference has no positional encoding")
    if cfg["mamba_n_groups"] != 1:
        raise ValueError("this reference shares B and C over all heads")
    eps = float(cfg["rms_norm_eps"])
    mult = float(cfg["residual_multiplier"])
    padded = np.zeros(length, np.int32)
    padded[:len(tokens)] = tokens
    with jax.default_matmul_precision("highest"):
        x = _embed(embed, jnp.asarray(padded),
                   mult=float(cfg["embedding_multiplier"]))
        for i, kind in enumerate(cfg["layer_types"]):
            if kind == "mamba":
                x = _mamba_layer(
                    x, layer(i), heads=cfg["mamba_n_heads"],
                    head_dim=cfg["mamba_d_head"], state=cfg["mamba_d_state"],
                    eps=eps, mult=mult)
            elif kind == "attention":
                x = _attention_layer(
                    x, layer(i), heads=cfg["num_attention_heads"],
                    kv_heads=cfg["num_key_value_heads"], eps=eps, mult=mult,
                    scale=float(cfg["attention_multiplier"]))
            else:
                raise ValueError(f"layer type {kind!r}")
        return _final(x, final_norm, eps=eps)


@partial(jax.jit, static_argnames=("scaling",))
def logit_rows(h, embed, chosen, *, scaling: float):
    """For hidden rows ``h`` (P, D) and one token id per row: the best
    logit, the logits' standard deviation over the vocabulary, and the
    logit of the chosen token.  ``embed`` is float32 (V, D); logits are
    divided by ``scaling`` (``logits_scaling``)."""
    logits = jnp.einsum("pd,vd->pv", h, embed, precision=_HI) / scaling
    best = jnp.max(logits, axis=-1)
    std = jnp.std(logits, axis=-1)
    pick = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return best, std, pick

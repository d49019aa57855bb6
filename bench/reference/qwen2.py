"""Plain Qwen2 forward pass in float32 (arXiv:2407.10671; the Hugging Face
``Qwen2ForCausalLM`` layer equations), written apart from the code under
test: it imports nothing of the program.

Per layer:

    h = RMSNorm(x) * ln1                       (eps from the config)
    q, k, v = h Wq + bq, h Wk + bk, h Wv + bv  (GQA: kv heads < heads)
    q, k = RoPE(q), RoPE(k)                    (rotate-half, theta)
    x = x + softmax(q k^T / sqrt(hd) + causal) v Wo
    x = x + (silu(RMSNorm(x) * ln2 Wg) * (RMSNorm(x) * ln2 Wu)) Wd

then the final RMSNorm and logits against the (tied) embedding.  Every
product runs at ``HIGHEST`` precision, since a float32 matmul on a TPU is
otherwise one bf16 pass.  One sequence at a time, one layer at a time, so
that it fits beside the served weights; every sequence is right-padded to
the same ``length`` (causal, so padding changes nothing before it), so that
one program serves them all.

Weights come as plain float32 arrays in this layout, per layer ``i``:
``ln1 (D,)``, ``q_w (D, H*hd)``, ``q_b (H*hd,)``, ``k_w (D, KV*hd)``,
``k_b``, ``v_w``, ``v_b``, ``o_w (H*hd, D)``, ``ln2 (D,)``, ``gate (D, F)``,
``up (D, F)``, ``down (F, D)``; and ``embed (V, D)``, ``final_norm (D,)``.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["hidden", "logit_rows"]

_HI = jax.lax.Precision.HIGHEST


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, heads, hd) at positions 0..S-1."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    half = hd // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


@partial(jax.jit, static_argnames=("heads", "kv_heads", "eps", "theta"))
def _layer(x, w, *, heads: int, kv_heads: int, eps: float, theta: float):
    S, D = x.shape
    hd = w["q_w"].shape[1] // heads
    dot = partial(jnp.matmul, precision=_HI)
    h = _rmsnorm(x, w["ln1"], eps)
    q = (dot(h, w["q_w"]) + w["q_b"]).reshape(S, heads, hd)
    k = (dot(h, w["k_w"]) + w["k_b"]).reshape(S, kv_heads, hd)
    v = (dot(h, w["v_w"]) + w["v_b"]).reshape(S, kv_heads, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=_HI).reshape(S, heads * hd)
    x = x + dot(o, w["o_w"])
    h = _rmsnorm(x, w["ln2"], eps)
    return x + dot(jax.nn.silu(dot(h, w["gate"])) * dot(h, w["up"]),
                   w["down"])


@partial(jax.jit, static_argnames=("eps",))
def _final(x, w, *, eps: float):
    return _rmsnorm(x, w, eps)


def hidden(cfg: Dict, layer: Callable[[int], Dict], embed, final_norm,
           tokens: np.ndarray, length: int) -> jax.Array:
    """Final-norm hidden states (length, D) of one token sequence of at
    most ``length`` tokens; rows past the sequence are padding.  ``cfg`` is
    the configuration file's dict (Hugging Face key names); ``layer(i)``
    gives layer i's weights in the layout above."""
    S = len(tokens)
    padded = np.zeros(length, np.int32)
    padded[:S] = tokens
    x = jnp.take(embed, jnp.asarray(padded), axis=0).astype(jnp.float32)
    kw = dict(heads=cfg["num_attention_heads"],
              kv_heads=cfg["num_key_value_heads"],
              eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]))
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, layer(i), **kw)
    return _final(x, final_norm, eps=float(cfg["rms_norm_eps"]))


@jax.jit
def logit_rows(h, embed, chosen):
    """For hidden rows ``h`` (P, D) and one token id per row: the best
    logit, the logits' standard deviation over the vocabulary, and the
    logit of the chosen token.  ``embed`` is float32 (V, D)."""
    logits = jnp.einsum("pd,vd->pv", h, embed, precision=_HI)
    best = jnp.max(logits, axis=-1)
    std = jnp.std(logits, axis=-1)
    pick = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
    return best, std, pick

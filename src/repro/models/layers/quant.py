"""Weight quantization for the serving fast path.

``QTensor`` packs a weight as symmetric per-output-channel int8 plus fp32
scales.  It is a registered pytree whose two children (``q``, ``scale``)
both carry any leading stacked "layers" axis, so a quantized weight rides
``jax.lax.scan`` over periods exactly like a plain array: the scan slices
period ``p`` out of both children and the layer sees a QTensor of the
original per-layer shape.

Layers dispatch on type: a plain ``jnp.ndarray`` keeps the literal einsum
(bit-identical to the fp32 path — the regression suites depend on this),
a ``QTensor`` routes through ``ops.quant_matmul`` (Pallas int8 kernel on
TPU, fp32-cast dequantized accumulation elsewhere — see that wrapper's
docstring for the exactness bound).

Weight layout convention (true for every projection in ``models/layers``):
the *contracted* axes lead and the *output-channel* axes trail, so
``n_contract`` pins the split — wq/wk/wv ``(d | h, hd)`` → n_contract 1,
wo ``(h, hd | d)`` → 2, mlp w_gate/w_up/w_down ``(d | f)`` / ``(f | d)``
→ 1, the SSD projections wz/w_xbc/wdt ``(d | ·)`` and w_out ``(di | d)``
→ 1.  ``scale`` has the output-channel (and any stacked) axes only.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.kernels import ops

__all__ = ["QTensor", "quantize_weight", "linear_or_quant",
           "quantize_model_params"]


@jax.tree_util.register_pytree_node_class
class QTensor:
    """int8 weight + per-output-channel fp32 scales.  ``q`` keeps the
    original weight shape; ``scale`` drops the ``n_contract`` contracted
    axes (which sit immediately after any stacked batch axes)."""

    def __init__(self, q, scale, n_contract: int):
        self.q = q
        self.scale = scale
        self.n_contract = n_contract

    def tree_flatten(self):
        return (self.q, self.scale), self.n_contract

    @classmethod
    def tree_unflatten(cls, n_contract, children):
        return cls(children[0], children[1], n_contract)

    def __repr__(self):
        return (f"QTensor(q={getattr(self.q, 'shape', None)}, "
                f"scale={getattr(self.scale, 'shape', None)}, "
                f"n_contract={self.n_contract})")


def quantize_weight(w: jnp.ndarray, n_contract: int,
                    n_batch: int = 0) -> QTensor:
    """Symmetric int8 quantization over the contracted axes (per output
    channel).  ``n_batch`` leading axes (the stacked "layers" axis) are
    kept on both ``q`` and ``scale`` so the result scans like the input."""
    axes = tuple(range(n_batch, n_batch + n_contract))
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=axes) / 127.0 + 1e-8
    sb = jnp.expand_dims(scale, axes)
    q = jnp.clip(jnp.round(wf / sb), -127, 127).astype(jnp.int8)
    return QTensor(q, scale, n_contract)


def _quant_contract(x: jnp.ndarray, w: QTensor) -> jnp.ndarray:
    """Contract ``x``'s trailing ``n_contract`` axes against ``w``'s leading
    ones: flatten both sides to a 2-D matmul, quantize the activation rows
    on the fly, and dequantize in the epilogue."""
    nc = w.n_contract
    K = math.prod(w.q.shape[:nc])
    out_shape = w.q.shape[nc:]
    xq, xs = ops.quantize_rows(x.reshape(-1, K))
    out = ops.quant_matmul(xq, xs, w.q.reshape(K, -1), w.scale.reshape(-1))
    return out.reshape(x.shape[:-nc] + out_shape).astype(x.dtype)


def linear_or_quant(x: jnp.ndarray, w, eq: str, **einsum_kwargs) -> jnp.ndarray:
    """The layer-side dispatch point: literal einsum for plain arrays
    (bit-identical to the pre-quantization code), quantized matmul for
    ``QTensor`` weights."""
    if isinstance(w, QTensor):
        return _quant_contract(x, w)
    return jnp.einsum(eq, x, w, **einsum_kwargs)


# weight name → n_contract, per module; everything absent stays fp32
# (biases, norms, embeddings, MoE experts, the SSD conv and recurrence
# parameters, RG-LRU mixers).
_QUANT_SPECS: Dict[str, Dict[str, int]] = {
    "attn": {"wq": 1, "wk": 1, "wv": 1, "wo": 2},
    "cross": {"wq": 1, "wk": 1, "wv": 1, "wo": 2},
    "mlp": {"w_gate": 1, "w_up": 1, "w_down": 1},
    "ssd": {"wz": 1, "w_xbc": 1, "wdt": 1, "w_out": 1},
}


def _quantize_blocks(blocks: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for bk, block in blocks.items():
        nb = dict(block)
        for mod, specs in _QUANT_SPECS.items():
            if mod in nb:
                m = dict(nb[mod])
                for name, nc in specs.items():
                    if name in m:
                        # stacked along the leading "layers" axis → n_batch=1
                        m[name] = quantize_weight(m[name], nc, n_batch=1)
                nb[mod] = m
        out[bk] = nb
    return out


def quantize_model_params(params: Dict[str, Any], mode: str) -> Dict[str, Any]:
    """Apply the ``ArchConfig.quantize`` knob to an initialized param tree.

    ``"none"`` returns the tree unchanged; ``"bf16"`` casts every floating
    leaf to bfloat16 (weight-only — activations keep the config dtype);
    ``"int8"`` quantizes the attention/MLP/SSD projections per
    ``_QUANT_SPECS`` and leaves everything else fp32."""
    if mode == "none":
        return params
    if mode == "bf16":
        return jax.tree.map(
            lambda a: a.astype(jnp.bfloat16)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    if mode != "int8":
        raise ValueError(f"quantize={mode!r} (want none|bf16|int8)")
    out = dict(params)
    out["blocks"] = _quantize_blocks(params["blocks"])
    if "encoder" in params:
        enc = dict(params["encoder"])
        enc["blocks"] = _quantize_blocks(enc["blocks"])
        out["encoder"] = enc
    return out

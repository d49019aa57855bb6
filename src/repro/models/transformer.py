"""Composable transformer stack driven by ArchConfig.

The decoder is ``num_periods`` repetitions of the config's block *pattern*;
parameters for each pattern position are stacked along a leading "layers"
axis and the stack executes as ONE ``jax.lax.scan`` over periods (HLO size —
and hence CPU compile time for the 70-compile dry-run matrix — stays
independent of depth).  Per-period caches ride along the same scan.

Public entry points:

    init_model(key, cfg)                 -> (params, axes)
    TransformerLM.forward(...)           -> logits (+ aux losses)   [train]
    TransformerLM.prefill(...)           -> logits, cache
    TransformerLM.decode_step(...)       -> logits, cache           [1 token]
    TransformerLM.init_cache(...)        -> cache pytree
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ATTENTION_KINDS, ArchConfig, BlockKind
from repro.models.layers.attention import attn_apply, attn_init, init_kv_cache
from repro.models.layers.embedding import (embed_init, embed_tokens,
                                           logits_from, sinusoidal_positions)
from repro.models.layers.mlp import mlp_apply, mlp_init
from repro.models.layers.moe import moe_apply, moe_init
from repro.models.layers.norms import (layernorm, layernorm_init, rmsnorm,
                                       rmsnorm_init)
from repro.models.layers.rglru import init_rglru_cache, rglru_apply, rglru_init
from repro.models.layers.ssd import init_ssd_cache, ssd_apply, ssd_init
from repro.models.params import split_tree_of, stack_bundles

__all__ = ["init_model", "TransformerLM", "RAGGED_KINDS"]

# blocks whose right-padded ragged prefill leaves each row's cache as its
# unpadded prefill would (``TransformerLM.prefill_ragged``)
RAGGED_KINDS = ATTENTION_KINDS + (BlockKind.SSD,)


def _norm_init(cfg: ArchConfig, dtype):
    return rmsnorm_init(cfg.d_model, dtype) if cfg.norm_kind == "rmsnorm" \
        else layernorm_init(cfg.d_model, dtype)


def _norm_apply(cfg: ArchConfig, params, x):
    return rmsnorm(params, x, cfg.norm_eps) if cfg.norm_kind == "rmsnorm" \
        else layernorm(params, x, cfg.norm_eps)


def _residual(cfg: ArchConfig, x, h):
    """x plus a block's branch h, scaled by ``residual_multiplier``."""
    if cfg.residual_multiplier != 1.0:
        h = h * cfg.residual_multiplier
    return x + h


def _embed(cfg: ArchConfig, params, tokens, positions=None):
    x = embed_tokens(params["embed"], tokens, positions)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _logits(cfg: ArchConfig, params, x):
    logits = logits_from(params["embed"], x)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


# --------------------------------------------------------------------------- #
# per-period init
# --------------------------------------------------------------------------- #
def _block_init(key: jax.Array, cfg: ArchConfig, kind: BlockKind, dtype,
                cross: bool):
    ks = jax.random.split(key, 6)
    mixed: Dict[str, Any] = {}
    mixed["ln1"] = split_tree_of(_norm_init(cfg, dtype))
    if kind in ATTENTION_KINDS:
        mixed["attn"] = attn_init(ks[0], cfg, dtype)
        if cross:
            mixed["ln_cross"] = split_tree_of(_norm_init(cfg, dtype))
            mixed["cross"] = attn_init(ks[1], cfg, dtype, cross=True)
        if cfg.mlp_kind != "none":
            mixed["ln2"] = split_tree_of(_norm_init(cfg, dtype))
            if cfg.num_experts > 0:
                mixed["moe"] = moe_init(ks[2], cfg, dtype)
            else:
                mixed["mlp"] = mlp_init(ks[3], cfg, dtype)
    elif kind == BlockKind.RGLRU:
        mixed["rglru"] = rglru_init(ks[0], cfg, dtype)
        if cfg.mlp_kind != "none":
            mixed["ln2"] = split_tree_of(_norm_init(cfg, dtype))
            mixed["mlp"] = mlp_init(ks[3], cfg, dtype)
    elif kind == BlockKind.SSD:
        mixed["ssd"] = ssd_init(ks[0], cfg, dtype)
        if cfg.mlp_kind != "none":
            mixed["ln2"] = split_tree_of(_norm_init(cfg, dtype))
            mixed["mlp"] = mlp_init(ks[3], cfg, dtype)
    else:
        raise ValueError(kind)
    params = {k: v[0] for k, v in mixed.items()}
    axes = {k: v[1] for k, v in mixed.items()}
    return params, axes


def _period_init(key: jax.Array, cfg: ArchConfig, dtype, cross: bool):
    params, axes = {}, {}
    ks = jax.random.split(key, len(cfg.pattern))
    for i, kind in enumerate(cfg.pattern):
        params[f"b{i}"], axes[f"b{i}"] = _block_init(ks[i], cfg, kind, dtype, cross)
    return params, axes


def init_model(key: jax.Array, cfg: ArchConfig) -> Tuple[Dict, Dict]:
    dtype = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4 + cfg.num_periods)
    params: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}

    params["embed"], axes["embed"] = embed_init(ks[0], cfg, dtype)

    periods = [
        _period_init(ks[4 + p], cfg, dtype, cross=cfg.cross_attention)
        for p in range(cfg.num_periods)
    ]
    params["blocks"], axes["blocks"] = stack_bundles(periods)

    params["final_norm"], axes["final_norm"] = split_tree_of(_norm_init(cfg, dtype))

    if cfg.encoder_layers > 0:
        enc_cfg = dataclasses.replace(cfg, causal=False, cross_attention=False,
                                      num_experts=0, pattern=(BlockKind.ATTN,),
                                      num_layers=cfg.encoder_layers)
        enc_periods = [
            _period_init(jax.random.fold_in(ks[1], p), enc_cfg, dtype, cross=False)
            for p in range(cfg.encoder_layers)
        ]
        enc: Dict[str, Any] = {}
        enc_axes: Dict[str, Any] = {}
        enc["blocks"], enc_axes["blocks"] = stack_bundles(enc_periods)
        enc["final_norm"], enc_axes["final_norm"] = split_tree_of(_norm_init(cfg, dtype))
        params["encoder"], axes["encoder"] = enc, enc_axes

    return params, axes


# --------------------------------------------------------------------------- #
# caches
# --------------------------------------------------------------------------- #
def _block_cache(cfg: ArchConfig, kind: BlockKind, batch: int, max_seq: int,
                 cross: bool, enc_seq: int, dtype):
    c: Dict[str, Any] = {}
    if kind in ATTENTION_KINDS:
        c["attn"] = init_kv_cache(cfg, kind, batch, max_seq, dtype)
        if cross:
            kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
            c["cross"] = {
                "k": jnp.zeros((batch, enc_seq, kv, hd), dtype),
                "v": jnp.zeros((batch, enc_seq, kv, hd), dtype),
            }
    elif kind == BlockKind.RGLRU:
        c["rglru"] = init_rglru_cache(cfg, batch, dtype)
    elif kind == BlockKind.SSD:
        c["ssd"] = init_ssd_cache(cfg, batch, dtype)
    return c


# --------------------------------------------------------------------------- #
# model
# --------------------------------------------------------------------------- #
class TransformerLM:
    """Stateless functional model bound to an ArchConfig."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    # ---------------- caches ---------------- #
    def init_cache(self, batch: int, max_seq: int) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        # one period's shapes, then each leaf made once at its stacked shape
        # (no per-period pieces to stack: a cache is never held twice)
        period = jax.eval_shape(lambda: {
            f"b{i}": _block_cache(cfg, kind, batch, max_seq,
                                  cfg.cross_attention, cfg.encoder_seq, dtype)
            for i, kind in enumerate(cfg.pattern)})
        return jax.tree.map(
            lambda s: jnp.zeros((cfg.num_periods,) + s.shape, s.dtype), period)

    # ---------------- encoder ---------------- #
    def encode(self, params: Dict, frames: jnp.ndarray) -> jnp.ndarray:
        """Whisper-style encoder over precomputed frame embeddings (stub
        frontend per the assignment carve-out): adds sinusoidal positions,
        runs bidirectional attention layers."""
        cfg = self.cfg
        enc_cfg = dataclasses.replace(cfg, causal=False)
        x = frames + sinusoidal_positions(frames.shape[1], cfg.d_model,
                                          frames.dtype)[None]

        def one_layer(x, lp):
            h = _norm_apply(cfg, lp["b0"]["ln1"], x)
            h, _ = attn_apply(lp["b0"]["attn"], h, cfg=enc_cfg, kind=BlockKind.ATTN,
                              mode="prefill", positions=jnp.arange(x.shape[1]),
                              use_rope=False)
            x = x + h
            h = _norm_apply(cfg, lp["b0"]["ln2"], x)
            x = x + mlp_apply(lp["b0"]["mlp"], h)
            return x, None

        body = jax.checkpoint(one_layer) if cfg.remat else one_layer
        if cfg.unroll_periods:
            for i in range(cfg.encoder_layers):
                x, _ = body(x, jax.tree.map(lambda t: t[i],
                                            params["encoder"]["blocks"]))
        else:
            x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
        return _norm_apply(cfg, params["encoder"]["final_norm"], x)

    # ---------------- block application ---------------- #
    def _apply_block(self, cfg: ArchConfig, kind: BlockKind, bp: Dict, x, *,
                     mode: str, positions=None, pos=None, cache=None,
                     memory=None, lengths=None, start_pos=None):
        aux = jnp.zeros((), jnp.float32)
        new_cache: Dict[str, Any] = {}
        if kind in ATTENTION_KINDS:
            h = _norm_apply(cfg, bp["ln1"], x)
            h, c = attn_apply(bp["attn"], h, cfg=cfg, kind=kind, mode=mode,
                              positions=positions, pos=pos,
                              cache=None if cache is None else cache.get("attn"),
                              use_rope=cfg.use_rope, lengths=lengths,
                              start_pos=start_pos)
            if c is not None:
                new_cache["attn"] = c
            x = _residual(cfg, x, h)
            has_cross_cache = cache is not None and "cross" in cache
            if "cross" in bp and (memory is not None or has_cross_cache):
                h = _norm_apply(cfg, bp["ln_cross"], x)
                h, cc = attn_apply(bp["cross"], h, cfg=cfg, kind=BlockKind.ATTN,
                                   mode=mode, positions=positions, pos=pos,
                                   cache=None if cache is None else cache.get("cross"),
                                   kv_src=memory, is_cross=True, use_rope=False)
                if cc is not None:
                    new_cache["cross"] = cc
                x = _residual(cfg, x, h)
            if "moe" in bp:
                h = _norm_apply(cfg, bp["ln2"], x)
                h, aux = moe_apply(bp["moe"], h, cfg)
                x = _residual(cfg, x, h)
        elif kind == BlockKind.RGLRU:
            h = _norm_apply(cfg, bp["ln1"], x)
            h, c = rglru_apply(bp["rglru"], h, cfg=cfg, mode=mode,
                               cache=None if cache is None else cache.get("rglru"))
            if c is not None:
                new_cache["rglru"] = c
            x = _residual(cfg, x, h)
        elif kind == BlockKind.SSD:
            h = _norm_apply(cfg, bp["ln1"], x)
            h, c = ssd_apply(bp["ssd"], h, cfg=cfg, mode=mode,
                             cache=None if cache is None else cache.get("ssd"),
                             lengths=lengths)
            if c is not None:
                new_cache["ssd"] = c
            x = _residual(cfg, x, h)
        if "mlp" in bp:
            h = _norm_apply(cfg, bp["ln2"], x)
            x = _residual(cfg, x, mlp_apply(bp["mlp"], h))
        return x, new_cache, aux

    def _run_stack(self, params, x, *, mode, positions=None, pos=None,
                   cache=None, memory=None, lengths=None, start_pos=None):
        cfg = self.cfg

        def period_fn(carry, scanned):
            x, aux_tot = carry
            if cache is None:
                pp, pc = scanned, None
            else:
                pp, pc = scanned
            new_pc: Dict[str, Any] = {}
            for i, kind in enumerate(cfg.pattern):
                x, nc, aux = self._apply_block(
                    cfg, kind, pp[f"b{i}"], x, mode=mode, positions=positions,
                    pos=pos, cache=None if pc is None else pc[f"b{i}"],
                    memory=memory, lengths=lengths, start_pos=start_pos)
                new_pc[f"b{i}"] = nc
                aux_tot = aux_tot + aux
            return (x, aux_tot), (new_pc if cache is not None else None)

        body = jax.checkpoint(period_fn) if (cfg.remat and mode != "decode") else period_fn
        xs = params["blocks"] if cache is None else (params["blocks"], cache)
        carry0 = (x, jnp.zeros((), jnp.float32))
        if cfg.unroll_periods:
            # Python loop (dry-run probes): every period appears in the HLO,
            # so cost_analysis counts all of them (scan bodies count once).
            carry = carry0
            caches = []
            for i in range(cfg.num_periods):
                xi = jax.tree.map(lambda t: t[i], xs)
                carry, pc = body(carry, xi)
                caches.append(pc)
            (x, aux) = carry
            new_cache = None if cache is None else jax.tree.map(
                lambda *cs: jnp.stack(cs, 0), *caches)
            return x, aux, new_cache
        (x, aux), new_cache = jax.lax.scan(body, carry0, xs)
        return x, aux, new_cache

    # ---------------- public entry points ---------------- #
    def forward(self, params: Dict, tokens: jnp.ndarray, *,
                vision_embeds: Optional[jnp.ndarray] = None,
                encoder_frames: Optional[jnp.ndarray] = None,
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Training forward: full-sequence logits.  Returns (logits, aux)."""
        x, aux = self.forward_hidden(params, tokens,
                                     vision_embeds=vision_embeds,
                                     encoder_frames=encoder_frames)
        return _logits(self.cfg, params, x), aux

    def forward_hidden(self, params: Dict, tokens: jnp.ndarray, *,
                       vision_embeds: Optional[jnp.ndarray] = None,
                       encoder_frames: Optional[jnp.ndarray] = None,
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Forward up to the final norm, WITHOUT the LM-head matmul —
        the chunked-loss path (§Perf) fuses logits into the loss instead.
        Returns (hidden (B, S, D), aux)."""
        cfg = self.cfg
        x = _embed(cfg, params, tokens,
                   jnp.arange(tokens.shape[1]) if cfg.learned_pos else None)
        if vision_embeds is not None:
            x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
        S = x.shape[1]
        positions = jnp.arange(S)
        memory = None
        if encoder_frames is not None:
            memory = self.encode(params, encoder_frames)
        x, aux, _ = self._run_stack(params, x, mode="prefill",
                                    positions=positions, memory=memory)
        x = _norm_apply(cfg, params["final_norm"], x)
        return x, aux

    def prefill(self, params: Dict, tokens: jnp.ndarray, cache: Dict, *,
                vision_embeds: Optional[jnp.ndarray] = None,
                encoder_frames: Optional[jnp.ndarray] = None):
        """Prefill: runs the full prompt, fills the cache, returns
        (last-token logits, cache)."""
        cfg = self.cfg
        x = _embed(cfg, params, tokens,
                   jnp.arange(tokens.shape[1]) if cfg.learned_pos else None)
        if vision_embeds is not None:
            x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
        S = x.shape[1]
        positions = jnp.arange(S)
        memory = None
        if encoder_frames is not None:
            memory = self.encode(params, encoder_frames)
        x, aux, new_cache = self._run_stack(params, x, mode="prefill",
                                            positions=positions, cache=cache,
                                            memory=memory)
        x = _norm_apply(cfg, params["final_norm"], x[:, -1:])
        return _logits(cfg, params, x), new_cache

    def prefill_ragged(self, params: Dict, tokens: jnp.ndarray,
                       lengths: jnp.ndarray, cache: Dict,
                       start_pos: Optional[jnp.ndarray] = None):
        """Mixed-length prefill for continuous batching: ``tokens`` is
        (B, S) with slot b's prompt *right-padded* — real tokens in columns
        0..lengths[b]-1, pad after.  Causal masking means a real token never
        attends a pad column, and the cache fill drops pad columns entirely
        (see ``_prefill_fill_cache``), so each slot's cache is exactly what
        a lone batch-1 prefill of its prompt would have written.  Returns
        (per-slot next-token logits (B, 1, V), cache).

        Restricted to dense stacks of attention and SSD blocks.  An SSD
        block leaves its state exactly where the row's last real token left
        it (Δ = 0 at pad positions, conv taps from the last real ones; see
        ``models/layers/ssd.py``).  An RG-LRU state would absorb the pad
        tail, and MoE capacity-factor routing couples slots through the
        shared token budget: those architectures prefill per-request
        instead (the serve engine handles the fallback).

        ``start_pos`` (B,) turns this into a **tail** prefill: slot b's
        tokens are the uncached suffix of its prompt, occupying absolute
        positions ``start_pos[b]..start_pos[b]+lengths[b]-1``, and the
        cache arrives with the prefix K/V already restored
        (``serve/prefix_cache.py``).  Rows with ``start_pos[b] == 0``
        degrade to a plain full prefill, so one compiled program serves
        waves mixing cache hits and misses.  It needs an attention-only
        stack: an SSD state cannot be restored from a prefix.
        """
        cfg = self.cfg
        if any(k not in RAGGED_KINDS for k in cfg.pattern):
            raise ValueError(f"{cfg.name}: prefill_ragged requires attention "
                             "and SSD blocks only (an RG-LRU state would "
                             "absorb the pad tail)")
        if start_pos is not None and any(k not in ATTENTION_KINDS
                                         for k in cfg.pattern):
            raise ValueError(f"{cfg.name}: a tail prefill (start_pos=) "
                             "needs an attention-only pattern")
        if cfg.num_experts or cfg.cross_attention or cfg.vision_tokens:
            raise ValueError(f"{cfg.name}: prefill_ragged supports dense "
                             "text-only decoders")
        lengths = jnp.asarray(lengths, jnp.int32)
        B, S = tokens.shape
        if start_pos is None:
            emb_pos = jnp.arange(S) if cfg.learned_pos else None
        else:
            start_pos = jnp.asarray(start_pos, jnp.int32)
            emb_pos = (start_pos[:, None] + jnp.arange(S)[None, :]
                       if cfg.learned_pos else None)
        x = _embed(cfg, params, tokens, emb_pos)
        positions = jnp.arange(S)
        x, aux, new_cache = self._run_stack(params, x, mode="prefill",
                                            positions=positions, cache=cache,
                                            lengths=lengths,
                                            start_pos=start_pos)
        # gather each slot's last *real* token (right-padding puts it at
        # column lengths[b]-1), then norm + LM head on (B, 1, D) only
        x_last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)
        x_last = _norm_apply(cfg, params["final_norm"], x_last)
        return _logits(cfg, params, x_last), new_cache

    def decode_step(self, params: Dict, token: jnp.ndarray, pos: jnp.ndarray,
                    cache: Dict):
        """One decode step.  token: (B, 1) int32; pos: scalar int32 (all
        rows at the same position) or (B,) int32 (continuous batching:
        per-slot positions).  Returns (logits (B,1,V), new_cache)."""
        cfg = self.cfg
        pos = jnp.asarray(pos, jnp.int32)
        if cfg.learned_pos:
            emb_pos = pos[:, None] if pos.ndim == 1 else pos[None]
        else:
            emb_pos = None
        x = _embed(cfg, params, token, emb_pos)
        x, aux, new_cache = self._run_stack(params, x, mode="decode", pos=pos,
                                            cache=cache)
        x = _norm_apply(cfg, params["final_norm"], x)
        return _logits(cfg, params, x), new_cache

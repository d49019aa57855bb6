"""Attention: GQA with RoPE/NoPE, global / sliding-window / chunked-local
variants, KV-cache decode (full and ring-buffer), optional int8 cache,
cross-attention for encoder–decoder stacks.

Memory discipline: prefill never materializes the full (S, S) logits — the
query dimension is processed in ``q_chunk`` blocks via ``lax.scan`` and each
block sees only the key span its mask admits:

    global/NoPE   key span = all keys ≤ chunk end      O(S·S) flops, O(S·C) mem
    sliding W     key span = C + W_pad                  O(S·W)
    chunked C_a   key span = its own attention chunk    O(S·C_a)

GQA contracts the queries in their kv-head groups: q is viewed as
(B, S, KV, H // KV, hd) and multiplied against K/V at their own KV heads, so
each K/V head (and each cache entry) is read once for its group of query
heads and never copied to query-head width.  Query head h pairs with kv head
h // (H // KV), the pairing ``jnp.repeat(k, H // KV, axis=2)`` would give.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig, BlockKind
from repro.models.layers.quant import linear_or_quant
from repro.models.layers.rope import apply_rope
from repro.models.params import bias as bias_init
from repro.models.params import linear, split_tree_of

__all__ = ["attn_init", "attn_apply", "init_kv_cache", "NEG_INF"]

NEG_INF = -2.0e38  # fp32-safe mask value


# --------------------------------------------------------------------------- #
# params
# --------------------------------------------------------------------------- #
def attn_init(key: jax.Array, cfg: ArchConfig, dtype, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    mixed: Dict[str, Any] = {
        "wq": linear(ks[0], (d, h, hd), ("embed", "heads", "head"), fan_in=d, dtype=dtype),
        "wk": linear(ks[1], (d, kv, hd), ("embed", "kv_heads", "head"), fan_in=d, dtype=dtype),
        "wv": linear(ks[2], (d, kv, hd), ("embed", "kv_heads", "head"), fan_in=d, dtype=dtype),
        "wo": linear(ks[3], (h, hd, d), ("heads", "head", "embed"), fan_in=h * hd, dtype=dtype),
    }
    if cfg.qkv_bias:
        mixed["bq"] = bias_init((h, hd), ("heads", "head"), dtype)
        mixed["bk"] = bias_init((kv, hd), ("kv_heads", "head"), dtype)
        mixed["bv"] = bias_init((kv, hd), ("kv_heads", "head"), dtype)
    return split_tree_of(mixed)


# --------------------------------------------------------------------------- #
# kv cache
# --------------------------------------------------------------------------- #
def init_kv_cache(cfg: ArchConfig, kind: BlockKind, batch: int, max_seq: int,
                  dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Cache for ONE attention layer.  Local/chunked layers keep a ring of
    ``window``/``attn_chunk`` slots; global layers keep ``max_seq``."""
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    if kind == BlockKind.ATTN_LOCAL:
        slots = min(cfg.window, max_seq)
    elif kind == BlockKind.ATTN_CHUNKED:
        slots = min(cfg.attn_chunk, max_seq)
    else:
        slots = max_seq
    if cfg.cache_dtype == "int8":
        return {
            "k": jnp.zeros((batch, slots, kv, hd), jnp.int8),
            "v": jnp.zeros((batch, slots, kv, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, slots, kv), jnp.float32),
            "v_scale": jnp.zeros((batch, slots, kv), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, slots, kv, hd), dtype),
        "v": jnp.zeros((batch, slots, kv, hd), dtype),
    }


def _quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _cache_write(cache: Dict[str, jnp.ndarray], slot: jnp.ndarray,
                 k_new: jnp.ndarray, v_new: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Write one token (B, kv, hd) at ring slot ``slot`` — a scalar (all
    batch rows share the position) or a (B,) vector (continuous-batching
    decode, where every slot sits at its own position)."""
    out = dict(cache)
    slot = jnp.asarray(slot)
    if slot.ndim == 0:
        idx = (slice(None), slot)
    else:
        idx = (jnp.arange(k_new.shape[0]), slot)
    if "k_scale" in cache:
        kq, ks = _quantize(k_new)
        vq, vs = _quantize(v_new)
        out["k"] = cache["k"].at[idx].set(kq)
        out["v"] = cache["v"].at[idx].set(vq)
        out["k_scale"] = cache["k_scale"].at[idx].set(ks)
        out["v_scale"] = cache["v_scale"].at[idx].set(vs)
    else:
        out["k"] = cache["k"].at[idx].set(k_new.astype(cache["k"].dtype))
        out["v"] = cache["v"].at[idx].set(v_new.astype(cache["v"].dtype))
    return out


def _cache_read(cache: Dict[str, jnp.ndarray]) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if "k_scale" in cache:
        k = cache["k"].astype(jnp.float32) * cache["k_scale"][..., None]
        v = cache["v"].astype(jnp.float32) * cache["v_scale"][..., None]
        return k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    return cache["k"], cache["v"]


# --------------------------------------------------------------------------- #
# core attention math
# --------------------------------------------------------------------------- #
def _sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
          mask: jnp.ndarray, scale: float) -> jnp.ndarray:
    """q: (B, Sq, H, hd)  k/v: (B, Sk, KV, hd)  mask: (B|1, 1, Sq, Sk) bool.
    The H query heads are contracted in KV groups of G = H // KV against
    their kv head (MHA is G = 1).  fp32 softmax, bf16 matmuls with fp32
    accumulation."""
    B, Sq, H, hd = q.shape
    kv = k.shape[2]
    qg = q.reshape(B, Sq, kv, H // kv, hd)
    logits = jnp.einsum("bqngd,bknd->bngqk", qg, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[:, :, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngqk,bknd->bqngd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# --------------------------------------------------------------------------- #
# prefill / train forward
# --------------------------------------------------------------------------- #
def _prefill_attend(q, k, v, kind: BlockKind, cfg: ArchConfig,
                    positions: jnp.ndarray) -> jnp.ndarray:
    """q: (B, S, H, hd), k/v: (B, S, KV, hd) at their own kv heads.
    positions: (S,) int32 absolute positions (shared across batch)."""
    B, S, H, hd = q.shape
    scale = cfg.attention_scale
    C = min(cfg.q_chunk, S)
    if S % C != 0:
        C = S  # fall back to single block (configs keep shapes divisible)
    n_chunks = S // C

    pos_q_all = positions
    pos_k_all = positions

    def mask_for(pq, pk):
        m = pk[None, :] <= pq[:, None] if cfg.causal else jnp.ones((pq.shape[0], pk.shape[0]), bool)
        if kind == BlockKind.ATTN_LOCAL:
            m = m & (pq[:, None] - pk[None, :] < cfg.window)
        elif kind == BlockKind.ATTN_CHUNKED:
            m = m & ((pq[:, None] // cfg.attn_chunk) == (pk[None, :] // cfg.attn_chunk))
        return m[None, None]  # (1, 1, Sq, Sk)

    if n_chunks == 1:
        return _sdpa(q, k, v, mask_for(pos_q_all, pos_k_all), scale)

    # key span per chunk kind
    if kind == BlockKind.ATTN_LOCAL:
        span = C + _round_up(cfg.window, C)
    elif kind == BlockKind.ATTN_CHUNKED:
        span = max(cfg.attn_chunk, C)
    else:
        span = S  # causal global: masked full span (flash kernel is the
        #           optimized path; see repro.kernels.flash_attention)

    q_c = q.reshape(B, n_chunks, C, H, hd)

    def body(_, i):
        qi = q_c[:, i]                                   # (B, C, H, hd)
        q_start = i * C
        pos_q = jax.lax.dynamic_slice_in_dim(pos_q_all, q_start, C)
        if span >= S:
            ki, vi, pos_k = k, v, pos_k_all
        else:
            if kind == BlockKind.ATTN_CHUNKED:
                start = (q_start // cfg.attn_chunk) * cfg.attn_chunk
            else:
                start = q_start + C - span
            start = jnp.clip(start, 0, S - span)
            ki = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
            vi = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            pos_k = jax.lax.dynamic_slice_in_dim(pos_k_all, start, span)
        out = _sdpa(qi, ki, vi, mask_for(pos_q, pos_k), scale)
        return None, out

    _, outs = jax.lax.scan(body, None, jnp.arange(n_chunks))
    # outs: (n_chunks, B, C, H, hd) -> (B, S, H, hd)
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd)


# --------------------------------------------------------------------------- #
# public apply
# --------------------------------------------------------------------------- #
def attn_apply(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    *,
    cfg: ArchConfig,
    kind: BlockKind,
    mode: str,                       # "prefill" | "decode"
    positions: Optional[jnp.ndarray] = None,   # (S,) prefill positions
    pos: Optional[jnp.ndarray] = None,         # scalar decode position
    cache: Optional[Dict[str, jnp.ndarray]] = None,
    kv_src: Optional[jnp.ndarray] = None,      # cross-attention source (B,Se,D)
    is_cross: bool = False,
    use_rope: bool = True,
    lengths: Optional[jnp.ndarray] = None,     # (B,) ragged prefill lengths
    start_pos: Optional[jnp.ndarray] = None,   # (B,) tail-prefill offsets
) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Returns (output (B,S,D), updated_cache_or_None)."""
    B, S, D = x.shape
    rope_on = use_rope and kind != BlockKind.ATTN_NOPE

    q = linear_or_quant(x, params["wq"], "bsd,dhk->bshk")
    if "bq" in params:
        q = q + params["bq"]

    cross = is_cross or kv_src is not None
    if cross and cache is not None and mode == "decode":
        # cross K/V were cached at prefill; nothing to project
        k, v = _cache_read(cache)
        new_cache = cache
    else:
        src = kv_src if cross else x
        k = linear_or_quant(src, params["wk"], "bsd,dhk->bshk")
        v = linear_or_quant(src, params["wv"], "bsd,dhk->bshk")
        if "bk" in params:
            k = k + params["bk"]
            v = v + params["bv"]
        new_cache = None

    if mode == "prefill":
        if cross:
            pos_q = positions if positions is not None else jnp.arange(S)
            if rope_on:
                q = apply_rope(q, pos_q[None, :], cfg.rope_base)
            mask = jnp.ones((1, 1, S, k.shape[1]), bool)
            out = _sdpa(q, k, v, mask, cfg.attention_scale)
            cross_cache = {"k": k, "v": v} if cache is not None else None
            return _out_proj(out, params), cross_cache
        if start_pos is not None:
            return _prefill_offset(params, q, k, v, cfg=cfg, kind=kind,
                                   cache=cache, lengths=lengths,
                                   start_pos=start_pos, rope_on=rope_on)
        pos_q = positions if positions is not None else jnp.arange(S)
        if rope_on:
            q = apply_rope(q, pos_q[None, :], cfg.rope_base)
            k = apply_rope(k, pos_q[None, :], cfg.rope_base)
        if cache is not None:
            # write the (possibly windowed) tail of K/V into the cache for
            # subsequent decode
            cache = _prefill_fill_cache(cache, k, v, lengths)
        out = _prefill_attend(q, k, v, kind, cfg, pos_q)
        return _out_proj(out, params), cache

    # ---------------- decode: S == 1, attend to cache ---------------- #
    # ``pos`` is a scalar (all rows at the same position) or a (B,) vector
    # (continuous batching: every slot decodes at its own position).  The
    # scalar form is the vector form with identical rows, so one code path
    # serves both.
    assert mode == "decode" and cache is not None and pos is not None
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    pos_b = pos if per_slot else jnp.broadcast_to(pos, (B,))   # (B,)
    if rope_on:
        q = apply_rope(q, pos_b[:, None], cfg.rope_base)
    if not cross:
        if rope_on:
            k = apply_rope(k, pos_b[:, None], cfg.rope_base)
        slots = cache["k"].shape[1]
        cache = _cache_write(cache, (pos_b if per_slot else pos) % slots,
                             k[:, 0], v[:, 0])
        kc, vc = _cache_read(cache)
        slot_ids = jnp.arange(slots)
        # most recent position ≡ slot (mod slots) that is ≤ pos, per row
        pc = pos_b[:, None]                                    # (B, 1)
        slot_pos = pc - (pc - slot_ids[None, :]) % slots       # (B, slots)
        valid = slot_pos >= 0
        if kind == BlockKind.ATTN_LOCAL:
            valid &= slot_pos > pc - cfg.window
        elif kind == BlockKind.ATTN_CHUNKED:
            valid &= (slot_pos // cfg.attn_chunk) == (pc // cfg.attn_chunk)
        else:
            valid &= slot_pos <= pc
        mask = valid[:, None, None, :]
        new_cache = cache
    else:
        kc, vc = _cache_read(cache)
        mask = jnp.ones((1, 1, 1, kc.shape[1]), bool)
        new_cache = cache
    out = _sdpa(q, kc, vc, mask, cfg.attention_scale)
    return _out_proj(out, params), new_cache


def _prefill_offset(params, q, k, v, *, cfg: ArchConfig, kind: BlockKind,
                    cache, lengths, start_pos, rope_on: bool):
    """Offset ragged prefill: slot b's tokens are its prompt *tail*,
    occupying absolute positions ``start_pos[b] .. start_pos[b]+lengths[b]-1``
    on top of a cache whose ring already holds the prefix K/V (restored by
    ``serve/prefix_cache.py``).  Tail queries attend the concatenation of
    the prefix cache (read BEFORE the tail write, so restored bits are
    attended verbatim) and the in-flight tail keys, each under its exact
    positional mask; rows with ``start_pos == 0`` see no valid prefix
    slot, so one compiled program serves hit and miss rows alike."""
    if cache is None or lengths is None:
        raise ValueError("start_pos= prefill needs cache= and lengths=")
    B, S = q.shape[0], q.shape[1]
    start = jnp.asarray(start_pos, jnp.int32)                  # (B,)
    L = jnp.asarray(lengths, jnp.int32)                        # (B,)
    pos_bq = start[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    if rope_on:
        q = apply_rope(q, pos_bq, cfg.rope_base)
        k = apply_rope(k, pos_bq, cfg.rope_base)
    kc, vc = _cache_read(cache)
    ring = cache["k"].shape[1]
    slot_ids = jnp.arange(ring, dtype=jnp.int32)[None, :]      # (1, ring)
    last = (start - 1)[:, None]                                # (B, 1)
    # the position each ring slot holds: largest value ≡ slot (mod ring)
    # that is ≤ start-1; negative ⇒ never written (start=0 rows: all)
    slot_pos = last - (last - slot_ids) % ring                 # (B, ring)
    mp = (slot_pos >= 0)[:, None, :]
    pq = pos_bq[:, :, None]                                    # (B, S, 1)
    if kind == BlockKind.ATTN_LOCAL:
        mp = mp & (pq - slot_pos[:, None, :] < cfg.window)
    elif kind == BlockKind.ATTN_CHUNKED:
        mp = mp & ((slot_pos[:, None, :] // cfg.attn_chunk)
                   == (pq // cfg.attn_chunk))
    else:
        # global: prefix positions (≤ start-1) precede every tail query,
        # so causality is automatic — the term only shapes mp to (B,S,r)
        mp = mp & (slot_pos[:, None, :] <= pq)
    # tail self-attention: ragged causal over real tail columns
    jk = jnp.arange(S, dtype=jnp.int32)[None, None, :]
    pk = pos_bq[:, None, :]                                    # (B, 1, S)
    mt = (jk < L[:, None, None]) & (pk <= pq)
    if kind == BlockKind.ATTN_LOCAL:
        mt = mt & (pq - pk < cfg.window)
    elif kind == BlockKind.ATTN_CHUNKED:
        mt = mt & ((pk // cfg.attn_chunk) == (pq // cfg.attn_chunk))
    mask = jnp.concatenate([mp, mt], axis=-1)[:, None]         # (B,1,S,r+S)
    k_all = jnp.concatenate([kc.astype(k.dtype), k], axis=1)
    v_all = jnp.concatenate([vc.astype(v.dtype), v], axis=1)
    out = _sdpa(q, k_all, v_all, mask, cfg.attention_scale)
    cache = _prefill_fill_cache(cache, k, v, lengths, start=start)
    return _out_proj(out, params), cache


def _prefill_fill_cache(cache, k, v, lengths=None, start=None):
    """Copy the last ``slots`` tokens of prefill K/V into the decode cache,
    laid out so ring addressing (slot = pos % slots) stays consistent.

    ``lengths=None`` is the classic equal-length path.  With ``lengths``
    (B,), the prompts are *right-padded* to a common S and slot b's real
    tokens occupy columns 0..lengths[b]-1: each row keeps the last
    ``min(lengths[b], slots)`` real columns and every pad / evicted column
    is routed to an out-of-bounds destination and dropped by the scatter —
    pad tokens never enter the cache, so the decode-side validity mask
    (slot_pos ≤ pos) stays exact per slot.

    ``start`` (B,) shifts row b's columns to absolute positions
    ``start[b]+j`` (tail prefill over a restored prefix — see
    ``_prefill_offset``): the kept window becomes the last ≤``slots``
    positions before ``start[b]+lengths[b]``, so prefix entries still
    inside the ring are never clobbered and the final ring state is
    exactly what a full prefill of the whole prompt would have left."""
    B, S = k.shape[0], k.shape[1]
    slots = cache["k"].shape[1]
    out = dict(cache)
    if lengths is None:
        take = min(S, slots)
        ks = k[:, S - take:]
        vs = v[:, S - take:]
        # position of ks[:, j] is (S - take + j); its slot is that mod slots
        pos0 = S - take
        dest = (pos0 + jnp.arange(take)) % slots
        if "k_scale" in cache:
            kq, ksc = _quantize(ks)
            vq, vsc = _quantize(vs)
            out["k"] = cache["k"].at[:, dest].set(kq)
            out["v"] = cache["v"].at[:, dest].set(vq)
            out["k_scale"] = cache["k_scale"].at[:, dest].set(ksc)
            out["v_scale"] = cache["v_scale"].at[:, dest].set(vsc)
        else:
            out["k"] = cache["k"].at[:, dest].set(ks.astype(cache["k"].dtype))
            out["v"] = cache["v"].at[:, dest].set(vs.astype(cache["v"].dtype))
        return out

    L = jnp.asarray(lengths, jnp.int32)[:, None]               # (B, 1)
    j = jnp.arange(S, dtype=jnp.int32)[None, :]                # (1, S)
    if start is None:
        keep = (j < L) & (j >= L - slots)  # last ≤slots real columns per row
        dest = jnp.where(keep, j % slots, slots)  # ``slots`` OOB → dropped
    else:
        s0 = jnp.asarray(start, jnp.int32)[:, None]            # (B, 1)
        abspos = s0 + j
        keep = (j < L) & (abspos >= s0 + L - slots)
        dest = jnp.where(keep, abspos % slots, slots)
    bidx = jnp.broadcast_to(jnp.arange(B, dtype=jnp.int32)[:, None], (B, S))
    if "k_scale" in cache:
        kq, ksc = _quantize(k)
        vq, vsc = _quantize(v)
        out["k"] = cache["k"].at[bidx, dest].set(kq, mode="drop")
        out["v"] = cache["v"].at[bidx, dest].set(vq, mode="drop")
        out["k_scale"] = cache["k_scale"].at[bidx, dest].set(ksc, mode="drop")
        out["v_scale"] = cache["v_scale"].at[bidx, dest].set(vsc, mode="drop")
    else:
        out["k"] = cache["k"].at[bidx, dest].set(
            k.astype(cache["k"].dtype), mode="drop")
        out["v"] = cache["v"].at[bidx, dest].set(
            v.astype(cache["v"].dtype), mode="drop")
    return out


def _out_proj(out: jnp.ndarray, params) -> jnp.ndarray:
    return linear_or_quant(out, params["wo"], "bshk,hkd->bsd")

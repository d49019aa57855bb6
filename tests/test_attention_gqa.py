"""Grouped-query attention contracts each K/V head against its group of
query heads and never copies K/V to query-head width.

Every way ``attn_apply`` reaches ``_sdpa`` (decode of each attention kind
over a full or ring cache, with a shared or per-slot position, the int8
cache, cross-attention, the multi-chunk prefill scan, the offset prefill over
a restored prefix) is run twice: as the program runs it, and with ``_sdpa``
replaced by a reference that repeats K/V to every query head with
``jnp.repeat`` and contracts head by head.  Both must agree, in float32 to
1e-5 and in bfloat16 within two units in the last place of the output's
largest entry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ArchConfig, BlockKind
from repro.models.layers import attention
from repro.models.layers.attention import attn_apply, attn_init, init_kv_cache

HEADS = [(12, 2), (32, 8), (4, 1), (8, 8)]   # (query heads, kv heads)
HD, D, B = 16, 32, 3
MAX_SEQ = 32
RING = 8            # window and attention chunk: local/chunked rings of 8
PREFILL_S = 24      # three q_chunk blocks of 8


def _repeat_sdpa(q, k, v, mask, scale):
    """K/V repeated to every query head, then one contraction per head."""
    groups = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask, logits, attention.NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _filled_cache(rng, cfg, kind, dtype):
    """A cache for ``kind`` whose every slot holds random K/V."""
    cache = init_kv_cache(cfg, kind, B, MAX_SEQ, dtype)
    out = {}
    for name, a in cache.items():
        if a.dtype == jnp.int8:
            out[name] = jnp.asarray(rng.integers(-127, 128, a.shape), jnp.int8)
        elif name.endswith("_scale"):
            out[name] = jnp.asarray(rng.uniform(0.002, 0.02, a.shape), a.dtype)
        else:
            out[name] = _normal(rng, a.shape, a.dtype)
    return out


def _normal(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


# case -> (kind, mode, what the call gets besides params and x)
CASES = {
    "decode-global-scalar": (BlockKind.ATTN, "decode", {"pos": 20}),
    "decode-global-per-slot": (BlockKind.ATTN, "decode", {"pos": [0, 13, 31]}),
    "decode-local-ring": (BlockKind.ATTN_LOCAL, "decode", {"pos": [5, 17, 30]}),
    "decode-chunked-ring": (BlockKind.ATTN_CHUNKED, "decode",
                            {"pos": [3, 12, 29]}),
    "decode-nope-scalar": (BlockKind.ATTN_NOPE, "decode", {"pos": 9}),
    "decode-int8-cache": (BlockKind.ATTN, "decode", {"pos": [4, 22, 31],
                                                     "int8": True}),
    "decode-cross": (BlockKind.ATTN, "decode", {"pos": 4, "cross": True}),
    "prefill-global-chunks": (BlockKind.ATTN, "prefill", {}),
    "prefill-local-chunks": (BlockKind.ATTN_LOCAL, "prefill", {}),
    "prefill-chunked-chunks": (BlockKind.ATTN_CHUNKED, "prefill", {}),
    "prefill-offset-global": (BlockKind.ATTN, "prefill", {"offset": True}),
    "prefill-offset-local": (BlockKind.ATTN_LOCAL, "prefill",
                             {"offset": True}),
    "prefill-cross": (BlockKind.ATTN, "prefill", {"cross": True}),
}


def _call(heads, case, dtype):
    """(params, x, attn_apply keyword arguments) for one case."""
    h, kv = heads
    kind, mode, extra = CASES[case]
    cfg = ArchConfig(name="gqa", num_layers=1, d_model=D, num_heads=h,
                     num_kv_heads=kv, head_dim=HD, d_ff=0, vocab_size=8,
                     window=RING, attn_chunk=RING, q_chunk=RING, dtype=dtype,
                     cache_dtype="int8" if extra.get("int8") else dtype)
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(h * 100 + kv)
    params, _ = attn_init(jax.random.PRNGKey(h * 100 + kv), cfg, dt)
    kw = {"cfg": cfg, "kind": kind, "mode": mode}
    if mode == "decode":
        x = _normal(rng, (B, 1, D), dt)
        kw["pos"] = jnp.asarray(extra["pos"], jnp.int32)
        if extra.get("cross"):
            kw["is_cross"] = True
            kw["cache"] = {"k": _normal(rng, (B, 10, kv, HD), dt),
                           "v": _normal(rng, (B, 10, kv, HD), dt)}
        else:
            kw["cache"] = _filled_cache(rng, cfg, kind, dt)
        return params, x, kw
    if extra.get("cross"):
        x = _normal(rng, (B, 6, D), dt)
        kw["kv_src"] = _normal(rng, (B, 10, D), dt)
        return params, x, kw
    if extra.get("offset"):
        x = _normal(rng, (B, RING, D), dt)
        kw["cache"] = _filled_cache(rng, cfg, kind, dt)
        kw["start_pos"] = jnp.asarray([0, 5, 20], jnp.int32)
        kw["lengths"] = jnp.asarray([RING, 3, RING], jnp.int32)
        return params, x, kw
    x = _normal(rng, (B, PREFILL_S, D), dt)
    kw["cache"] = init_kv_cache(cfg, kind, B, MAX_SEQ, dt)
    return params, x, kw


def _jitted(static):
    """``attn_apply`` jitted anew: a fresh function, so no program traced
    before ``_sdpa`` was replaced is reused after."""
    return jax.jit(lambda p, x, a: attn_apply(p, x, **static, **a))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("heads", HEADS, ids=lambda hk: f"{hk[0]}q{hk[1]}kv")
def test_grouped_attention_matches_repeated_kv(monkeypatch, heads, case,
                                               dtype):
    params, x, kw = _call(heads, case, dtype)
    static = {k: kw.pop(k) for k in ("cfg", "kind", "mode", "is_cross")
              if k in kw}
    out, cache = _jitted(static)(params, x, kw)
    monkeypatch.setattr(attention, "_sdpa", _repeat_sdpa)
    ref_out, ref_cache = _jitted(static)(params, x, kw)

    assert out.shape == ref_out.shape == x.shape
    assert out.dtype == ref_out.dtype
    got = np.asarray(out, np.float32)
    want = np.asarray(ref_out, np.float32)
    assert np.all(np.isfinite(want))
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:
        ulp = 2.0 ** (np.floor(np.log2(np.abs(want).max())) - 7)
        np.testing.assert_allclose(got, want, rtol=0, atol=2 * ulp)
    if cache is not None:
        jax.tree.map(np.testing.assert_array_equal, cache, ref_cache)


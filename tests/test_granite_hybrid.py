"""granite-4.0-h-micro's hybrid stack (Mamba-2 SSD and NoPE attention
layers, each followed by a SwiGLU MLP, with the granite multipliers) at a
small size on the CPU: one whole period of 10 layers at tiny widths, seeded
random weights, against the plain float32 reference in
``bench/reference/granite_hybrid.py`` (the per-position recurrence, written
apart from the program).  Also: the SSD pad rule of the ragged prefill, a
reused slot, and the numbers of the other configs, which the new fields
must leave as they were."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.drivers.serve_hybrid import layer_view  # noqa: E402
from bench.reference import granite_hybrid as ref  # noqa: E402
from repro.configs import ARCH_IDS, get_smoke  # noqa: E402
from repro.configs.granite_4_0_h_micro import FULL, SMOKE  # noqa: E402
from repro.models.config import BlockKind  # noqa: E402
from repro.models.layers.ssd import init_ssd_cache, ssd_apply  # noqa: E402
from repro.models.transformer import TransformerLM, init_model  # noqa: E402
from repro.serve import Request, ServeEngine  # noqa: E402

CFG = SMOKE
MAX_SEQ = 64
# float32 on both sides; the program's chunked dual form and the
# reference's per-position recurrence sum the same terms in another order,
# which moves the logits by about 1e-6 of their spread here
LOGIT_TOL = 1e-4


def _hf(cfg):
    """The reference's configuration (Hugging Face names) of an ArchConfig."""
    kinds = {BlockKind.SSD: "mamba", BlockKind.ATTN_NOPE: "attention"}
    return {"position_embedding_type": "nope", "mamba_n_groups": 1,
            "rms_norm_eps": cfg.norm_eps,
            "residual_multiplier": cfg.residual_multiplier,
            "embedding_multiplier": cfg.embedding_multiplier,
            "attention_multiplier": cfg.attention_scale,
            "layer_types": [kinds[k] for k in cfg.pattern] * cfg.num_periods,
            "mamba_n_heads": cfg.ssd_heads, "mamba_d_head": cfg.ssd_head_dim,
            "mamba_d_state": cfg.ssm_state,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads}


@pytest.fixture(scope="module")
def params():
    """Seeded weights.  Δ's bias as Mamba-2 draws it (softplus^-1 of a
    log-uniform Δ in [0.001, 0.1]), so that the state carries across a
    prompt; embeddings a tenth of init_model's, so that at this width the
    embeddings times 12 do not drown the branches times 0.22 and the
    served tokens depend on the state."""
    p, _ = init_model(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(0)
    for b, kind in enumerate(CFG.pattern):
        if kind == BlockKind.SSD:
            ssd = p["blocks"][f"b{b}"]["ssd"]
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                    ssd["dt_bias"].shape))
            ssd["dt_bias"] = jnp.asarray(dt + np.log(-np.expm1(-dt)),
                                         jnp.float32)
    p["embed"]["tok"] = p["embed"]["tok"] * 0.1
    return p


def _reference_logits(params, tokens):
    h = ref.hidden(_hf(CFG), lambda i: layer_view(CFG, params, i),
                   params["embed"]["tok"], 1.0 + params["final_norm"]["scale"],
                   np.asarray(tokens, np.int32), len(tokens))
    return np.asarray(h @ params["embed"]["tok"].T / CFG.logits_scaling)


def _prompts(lens, seed=1):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, size=n).astype(np.int32)
            for n in lens]


def test_full_config_is_the_published_one():
    assert (FULL.num_layers, FULL.d_model, FULL.num_heads, FULL.num_kv_heads,
            FULL.resolved_head_dim, FULL.d_ff, FULL.vocab_size) == (
        40, 2048, 32, 8, 64, 8192, 100_352)
    assert (FULL.ssd_heads, FULL.ssd_head_dim, FULL.d_inner_ssd,
            FULL.ssm_state, FULL.conv_width, FULL.ssd_chunk) == (
        64, 64, 4096, 128, 4, 256)
    layers = list(FULL.pattern) * FULL.num_periods
    assert [i for i, k in enumerate(layers)
            if k == BlockKind.ATTN_NOPE] == [5, 15, 25, 35]
    assert layers.count(BlockKind.SSD) == 36
    assert (FULL.attention_scale, FULL.embedding_multiplier,
            FULL.residual_multiplier, FULL.logits_scaling, FULL.norm_eps) == (
        1 / 64, 12.0, 0.22, 8.0, 1e-5)
    n = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda k: init_model(k, FULL)[0], jax.random.PRNGKey(0))))
    assert 3.18e9 < n < 3.20e9


def test_forward_matches_reference(params):
    tokens = _prompts([40])[0]
    got = np.asarray(TransformerLM(CFG).forward(params, tokens[None])[0][0])
    want = _reference_logits(params, tokens)
    spread = want.max() - want.min()
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL * spread, rtol=0)


def test_ragged_prefill_then_decode_matches_reference(params):
    """Mixed-length prompts admitted in ragged waves into three slots and
    decoded through ServeEngine.run: the logits of every prefill row and
    every decode row of a request equal the reference's full forward over
    its prompt and served tokens at that position, and every served token
    is the reference's first choice."""
    from repro.serve import SlotScheduler

    engine = ServeEngine(CFG, params, batch_size=3, max_seq=MAX_SEQ)
    assert engine.ragged_ok
    sched = SlotScheduler(engine.batch)
    rows = []                               # (request, position, logits)
    prefill, decode = engine._prefill_ragged, engine._decode

    def recording_prefill(p, t, n):
        out = prefill(p, t, n)
        for i, length in enumerate(np.asarray(n)):
            for r in reqs:
                if len(r.prompt) == length and np.array_equal(
                        np.asarray(t[i, :length]), r.prompt):
                    rows.append((r, length - 1, np.asarray(out[0][i, 0])))
        return out

    def recording_decode(p, t, pos, c):
        out = decode(p, t, pos, c)
        for s, r in enumerate(sched.slots):
            if r is not None:
                rows.append((r, int(pos[s]), np.asarray(out[0][s, 0])))
        return out

    engine._prefill_ragged = recording_prefill
    engine._decode = recording_decode
    reqs = [Request(prompt=p, max_new_tokens=n)
            for p, n in zip(_prompts([5, 17, 30, 11, 23]), (9, 6, 12, 7, 5))]
    engine.run(reqs, scheduler=sched)
    want = {}
    for r in reqs:
        assert r.done and len(r.out_tokens) == r.max_new_tokens
        seq = np.concatenate([r.prompt, np.asarray(r.out_tokens)])
        want[id(r)] = _reference_logits(params, seq)
        served = want[id(r)][len(r.prompt) - 1:-1]
        np.testing.assert_array_equal(np.argmax(served, -1), r.out_tokens)
    # every served token's logits, and the steps past a request's last
    # token (its slot decodes once more before it retires) where in range
    compared = 0
    for r, at, logits in rows:
        if at < len(want[id(r)]):
            w = want[id(r)][at]
            np.testing.assert_allclose(logits, w, rtol=0,
                                       atol=LOGIT_TOL * (w.max() - w.min()))
            compared += 1
    assert compared >= sum(r.max_new_tokens for r in reqs)


def test_reused_slot_gives_a_fresh_slots_logits(params):
    """One slot: the second request is admitted where the first one ran and
    retired.  Its decode logits equal those of the same request on an
    engine that never served anything."""
    first, second = _prompts([30, 12], seed=4)

    def decode_logits(reqs):
        engine = ServeEngine(CFG, params, batch_size=1, max_seq=MAX_SEQ)
        seen = []
        decode = engine._decode

        def recording(p, t, pos, c):
            out = decode(p, t, pos, c)
            seen.append(np.asarray(out[0]))
            return out

        engine._decode = recording
        engine.run(reqs)
        return seen

    reused = decode_logits([Request(prompt=first, max_new_tokens=10),
                            Request(prompt=second, max_new_tokens=8)])
    fresh = decode_logits([Request(prompt=second, max_new_tokens=8)])
    assert len(fresh) == 7 and len(reused) == 9 + 7
    np.testing.assert_array_equal(np.stack(reused[-7:]), np.stack(fresh))


@pytest.mark.parametrize("length", [2, 9, 20])
def test_ssd_pad_tail_leaves_state_as_the_unpadded_row(length):
    """A row right-padded to 32 columns, prefilled with its length, leaves
    ``h`` and the conv taps as its own unpadded prefill does: Δ = 0 at pad
    columns, the taps from the last real columns (reaching into the initial
    taps for a row shorter than them)."""
    cfg = dataclasses.replace(CFG, ssd_chunk=16)
    from repro.models.layers.ssd import ssd_init

    p, _ = ssd_init(jax.random.PRNGKey(3), cfg, jnp.float32)
    p["dt_bias"] = jnp.full_like(p["dt_bias"], -3.0)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 32, cfg.d_model))
    padded = x.at[:, length:].set(jax.random.normal(
        jax.random.PRNGKey(5), (1, 32 - length, cfg.d_model)))
    cache = init_ssd_cache(cfg, 1, jnp.float32)
    cache["conv"] = jax.random.normal(jax.random.PRNGKey(6),
                                      cache["conv"].shape)
    out_p, got = ssd_apply(p, padded, cfg=cfg, mode="prefill", cache=cache,
                           lengths=jnp.asarray([length]))
    out_u, want = ssd_apply(p, x[:, :length], cfg=cfg, mode="prefill",
                            cache=cache)
    # the same chunks hold the same real terms; float32, summed in the
    # same order up to the pad tail's exact zeros
    np.testing.assert_allclose(got["h"], want["h"], rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(got["conv"], want["conv"])
    np.testing.assert_allclose(out_p[:, :length], out_u, rtol=1e-6,
                               atol=1e-6)


# forward logits, prefill logits and one decode step of every other smoke
# config, on fixed tokens, as the code before the granite fields computed
# them with GQA contracted in kv-head groups: sums of all logits, of the
# last position's first 8, of the prefill logits, of the decode logits and
# of row 1's first 8.  mamba2 is not here: its conv now runs over xBC, as
# published.
BEFORE = {
    "llama4-scout-17b-16e": (424.23037925111566, 3.3938805758953094,
                             18.036012916680193, -1.8165518603054807,
                             1.2160989046096802),
    "mixtral-8x22b": (170.16474323920556, 8.317782938480377,
                      -53.95307354955003, 23.660836843191646,
                      2.6517725586891174),
    "whisper-small": (1200.080329366534, 3.306814521551132,
                      66.57426917299745, 49.87222117872443,
                      3.938631370663643),
    "granite-3-8b": (-268.8813643330359, 2.572710707783699,
                     120.4312344477512, 137.02978613879532,
                     5.516932487487793),
    "llava-next-34b": (-87.2040806554869, -0.8827031254768372,
                       139.33390398003394, 125.27101026917808,
                       5.660919964313507),
    "qwen1.5-32b": (603.5560335336922, -6.159585773944855,
                    20.22703503369121, 52.292271421523765,
                    -3.503520578145981),
    "recurrentgemma-9b": (-200.25025446222935, -0.6831352552399039,
                          37.160228891065344, 62.0592256935779,
                          0.403882160782814),
    "gemma3-1b": (-620.9372202632367, -3.5384716540575027,
                  -96.03266918403096, -101.02128124109004,
                  -2.4666707515716553),
    "qwen2-1.5b": (-268.8813643330359, 2.572710707783699,
                   120.4312344477512, 137.02978613879532,
                   5.516932487487793),
}


@pytest.mark.parametrize("arch", sorted(BEFORE))
def test_new_defaults_leave_other_configs_numbers_unchanged(arch):
    assert set(BEFORE) == set(ARCH_IDS) - {"mamba2-2.7b"}
    cfg = get_smoke(arch)
    assert (cfg.attn_scale, cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (None, 1.0, 1.0, 1.0)
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    m = TransformerLM(cfg)
    B, S = 2, 16
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, S)), jnp.int32)
    kw = {}
    if cfg.vision_tokens:
        kw["vision_embeds"] = jnp.ones((B, cfg.vision_tokens, cfg.d_model),
                                       jnp.dtype(cfg.dtype)) * 0.01
    if cfg.encoder_layers:
        kw["encoder_frames"] = jnp.ones((B, 8, cfg.d_model),
                                        jnp.dtype(cfg.dtype)) * 0.01
    logits, _ = m.forward(params, toks, **kw)
    pl, cache = m.prefill(params, toks, m.init_cache(B, 64), **kw)
    dl, _ = m.decode_step(params, toks[:, :1],
                          jnp.asarray(S + (cfg.vision_tokens or 0)), cache)
    f = lambda a: np.asarray(a, np.float64)  # noqa: E731
    got = (f(logits).sum(), f(logits)[0, -1, :8].sum(), f(pl).sum(),
           f(dl).sum(), f(dl)[1, 0, :8].sum())
    # the same operations in the same order: equal up to XLA:CPU's
    # reduction order between processes
    np.testing.assert_allclose(got, BEFORE[arch], rtol=1e-6, atol=1e-6)

"""Driver for the serving cells of a hybrid stack of Mamba-2 (SSD) and
attention layers (granite-4.0-h-micro): an open-loop request stream through
``ServeEngine.run`` with a ``SlotScheduler``, as ``bench/drivers/serve.py``
drives a dense decoder, with this architecture's configuration, weights,
reference and counts.

Set-up makes the weights on the device from the seed, in bfloat16: normals
of the configuration's standard deviations by leaf name, then the SSD
leaves as Mamba-2 initialises them (``A_log`` = log U[1, 16], ``D`` = 1,
``dt_bias`` the inverse softplus of a Δ log-uniform in [0.001, 0.1], the
gated norm's weight 1).  It builds the engine, which must take the ragged
prefill path, and warms the prefill buckets this run's requests use, the
cache scatter and the decode step.  The window and the token stamps are
``bench/drivers/serve.py``'s.

Counts for the per-layer readers: the program's counters
(``repro.core.trace``: ``serve.admit_waves``, ``serve.prefill_tokens``,
``serve.prefill_pad_tokens``) over the window; in a traced run, the
program's ``serve.*`` host spans of the traced slice, read from the trace
before the harness reads it, and the slice's decode steps, live positions,
state bytes and model FLOPs.  The device's idle time in the slice is
logged by the innermost ``serve.*`` span open over it.

Correctness: after the drain, the longest finished request and others
drawn from the seed go through the plain float32 reference
(``bench/reference/granite_hybrid.py``) over prompt and served tokens; at
every served token, (the reference's best logit - its logit for the served
token) / the standard deviation of its logits there is a gap, and the
widest gap is compared with its limit.  The control (``control_tokens``),
for calibration only, is the program's int8 weight path, teacher-forced.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import program_trace, trace_reduce
from bench.drivers.fit import jax_key
from bench.drivers.serve import (TraceSlice, make_params, sample_for_check,
                                 serve_window, steps, window_metrics)
from bench.reference import granite_hybrid as ref
from bench.traffic import serving_requests

PROGRAM_SPANS = "serve."
# Mamba-2's initialisation (arXiv:2405.21060; mamba_ssm's Mamba2)
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)


def arch_config(cfg: Dict, **overrides):
    """The program's ``ArchConfig`` for the configuration file (Hugging
    Face key names), with nothing changed from it.  The period of the
    layer pattern is the shortest that repeats ``layer_types``."""
    from repro.models.config import ArchConfig, BlockKind

    kinds = {"mamba": BlockKind.SSD, "attention": BlockKind.ATTN_NOPE}
    if (cfg["position_embedding_type"] != "nope" or cfg["num_local_experts"]
            or cfg["mamba_n_groups"] != 1 or cfg["attention_bias"]
            or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"]):
        raise ValueError("the program's hybrid stack is NoPE attention, "
                         "dense MLPs, one SSD group, biases on the conv only")
    if cfg["mamba_expand"] * cfg["hidden_size"] != (
            cfg["mamba_n_heads"] * cfg["mamba_d_head"]):
        raise ValueError("d_inner must be mamba_n_heads x mamba_d_head")
    layers = [kinds[t] for t in cfg["layer_types"]]
    period = next(p for p in range(1, len(layers) + 1)
                  if len(layers) % p == 0
                  and layers == layers[:p] * (len(layers) // p))
    return ArchConfig(
        name=cfg["name"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["shared_intermediate_size"], vocab_size=cfg["vocab_size"],
        pattern=tuple(layers[:period]),
        attn_scale=float(cfg["attention_multiplier"]),
        ssm_state=cfg["mamba_d_state"], ssd_head_dim=cfg["mamba_d_head"],
        ssd_expand=cfg["mamba_expand"], ssd_chunk=cfg["mamba_chunk_size"],
        conv_width=cfg["mamba_d_conv"], use_rope=False,
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        max_seq_len=cfg["max_position_embeddings"], dtype=cfg["torch_dtype"],
        **overrides)


def _ssd_leaf(name: str, key, shape, dtype):
    """An SSD leaf as Mamba-2 initialises it, or None for a plain normal."""
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          *A_RANGE)).astype(dtype)
    if name in ("D", "norm_scale"):
        return jnp.ones(shape, dtype)
    if name == "dt_bias":
        lo, hi = np.log(DT_RANGE[0]), np.log(DT_RANGE[1])
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)   # softplus^-1
    return None


def make_hybrid_params(seed: int, arch, weights: Dict):
    """The served weights: ``make_params``'s normals, then the SSD leaves
    that Mamba-2 initialises otherwise made anew from the seed."""
    params = make_params(seed, arch, weights)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.fold_in(jax_key(seed), 1), len(flat))
    leaves = []
    for (path, leaf), k in zip(flat, keys):
        name = str(getattr(path[-1], "key", path[-1]))
        new = _ssd_leaf(name, k, leaf.shape, leaf.dtype)
        leaves.append(leaf if new is None else new)
    return jax.block_until_ready(jax.tree_util.tree_unflatten(treedef, leaves))


def build_engine(cfg: Dict, seed: int):
    """The engine over weights made from ``seed``, and those weights as the
    benchmark made them (the reference reads these)."""
    from repro.serve import ServeEngine

    arch = arch_config(cfg)
    params = make_hybrid_params(seed, arch, cfg["weights"])
    serving = cfg["serving"]
    engine = ServeEngine(arch, params, batch_size=serving["slots"],
                         max_seq=serving["max_seq"], pad_to=serving["pad_to"])
    if not engine.ragged_ok:
        raise ValueError("the hybrid stack must take the ragged prefill path")
    return engine, params


# --------------------------------------------------------------------------- #
# the reference
# --------------------------------------------------------------------------- #
def layer_view(arch, params, i: int) -> Dict:
    """Layer ``i`` of the program's parameter tree in the reference's plain
    float32 layout."""
    p, b = divmod(i, len(arch.pattern))
    blk = params["blocks"][f"b{b}"]
    f32 = lambda x: x[p].astype(jnp.float32)  # noqa: E731
    m = blk["mlp"]
    out = {"ln1": 1.0 + f32(blk["ln1"]["scale"]),
           "ln2": 1.0 + f32(blk["ln2"]["scale"]),
           "gate": f32(m["w_gate"]), "up": f32(m["w_up"]),
           "down": f32(m["w_down"])}
    if "ssd" in blk:
        s = blk["ssd"]
        out.update(in_z=f32(s["wz"]), in_xbc=f32(s["w_xbc"]),
                   in_dt=f32(s["wdt"]), conv_w=f32(s["conv_w"]),
                   conv_b=f32(s["conv_b"]), dt_bias=f32(s["dt_bias"]),
                   A_log=f32(s["A_log"]), D=f32(s["D"]),
                   norm=f32(s["norm_scale"]), out=f32(s["w_out"]))
    else:
        a = blk["attn"]
        flat = lambda x: f32(x).reshape(arch.d_model, -1)  # noqa: E731
        out.update(q_w=flat(a["wq"]), k_w=flat(a["wk"]), v_w=flat(a["wv"]),
                   o_w=f32(a["wo"]).reshape(-1, arch.d_model))
    return out


def _hidden(cfg: Dict, arch, params, r):
    served = np.asarray(list(r.out_tokens), np.int32)
    seq = np.concatenate([r.prompt, served[:-1]]).astype(np.int32)
    return ref.hidden(cfg, lambda i: layer_view(arch, params, i),
                      params["embed"]["tok"].astype(jnp.float32),
                      1.0 + params["final_norm"]["scale"].astype(jnp.float32),
                      seq, cfg["serving"]["max_seq"])


def _rows(h, first: int, n: int, chunk: int):
    """Blocks of ``chunk`` hidden rows from ``first``, as (start, rows)."""
    h = jnp.pad(h, ((0, chunk), (0, 0)))
    for s in range(0, n, chunk):
        yield s, jax.lax.dynamic_slice_in_dim(h, first + s, chunk)


def reference_gaps(cfg: Dict, params, requests, chosen=None,
                   chunk: int = 512) -> np.ndarray:
    """The normalised gap, under the float32 reference, of a token at every
    served position of ``requests``: (best logit - the token's logit) / the
    logits' standard deviation.  The token is the served one, or
    ``chosen[i]`` (one id per served token of request i) where given."""
    arch = arch_config(cfg)
    embed = params["embed"]["tok"].astype(jnp.float32)
    scaling = float(cfg["logits_scaling"])
    out = []
    for i, r in enumerate(requests):
        served = np.asarray(list(r.out_tokens), np.int32)
        picks = served if chosen is None else np.asarray(chosen[i], np.int32)
        h = _hidden(cfg, arch, params, r)
        for s, rows in _rows(h, len(r.prompt) - 1, len(served), chunk):
            n = min(chunk, len(served) - s)
            block = np.zeros(chunk, np.int32)
            block[:n] = picks[s:s + n]
            best, std, pick = ref.logit_rows(rows, embed, jnp.asarray(block),
                                             scaling=scaling)
            out.append(np.asarray((best - pick) / std, np.float64)[:n])
    return np.concatenate(out)


def control_tokens(cfg: Dict, params, requests) -> List[np.ndarray]:
    """The lower-precision control: per request, the token the program's
    own int8 weight path (``quantize="int8"``: attention, MLP and SSD
    projections) puts first at every served position, teacher-forced over
    prompt + served tokens (read through ``reference_gaps(chosen=...)``)."""
    from repro.models.layers.quant import quantize_model_params
    from repro.models.transformer import TransformerLM

    model = TransformerLM(arch_config(cfg, quantize="int8"))
    qparams = quantize_model_params(params, "int8")

    @jax.jit
    def first_choice(p, tokens, at):
        h, _ = model.forward_hidden(p, tokens)
        h = jnp.take_along_axis(h, at[None, :, None], axis=1)[0]
        return jnp.argmax(jnp.einsum("pd,vd->pv", h, p["embed"]["tok"],
                                     preferred_element_type=jnp.float32),
                          axis=-1)

    length = cfg["serving"]["max_seq"]
    out = []
    for r in requests:
        served = np.asarray(list(r.out_tokens), np.int32)
        seq = np.concatenate([r.prompt, served[:-1]]).astype(np.int32)
        tokens = np.zeros((1, length), np.int32)
        tokens[0, :len(seq)] = seq
        at = np.zeros(length, np.int32)
        at[:len(served)] = len(r.prompt) - 1 + np.arange(len(served))
        out.append(np.asarray(first_choice(qparams, jnp.asarray(tokens),
                                           jnp.asarray(at)))[:len(served)])
    return out


# --------------------------------------------------------------------------- #
# counts for the per-layer readers
# --------------------------------------------------------------------------- #
def _ssd_layers(arch) -> int:
    from repro.models.config import BlockKind

    return arch.pattern.count(BlockKind.SSD) * arch.num_periods


def state_bytes(arch, slots: int) -> int:
    """Every slot's recurrent state: float32 ``h`` and the conv taps."""
    di, N = arch.d_inner_ssd, arch.ssm_state
    per_layer = (arch.ssd_heads * arch.ssd_head_dim * N * 4
                 + (arch.conv_width - 1) * (di + 2 * N)
                 * jnp.dtype(arch.dtype).itemsize)
    return slots * _ssd_layers(arch) * per_layer


def kv_bytes_per_position(arch) -> int:
    attn = arch.num_layers - _ssd_layers(arch)
    return (2 * attn * arch.num_kv_heads * arch.resolved_head_dim
            * jnp.dtype(arch.dtype).itemsize)


def model_flops(arch, context: np.ndarray, heads: int) -> float:
    """Forward FLOPs of one token at each of the given context lengths,
    ``heads`` of them through the LM head.  Per token: 2 x every layer's
    projection and MLP weights; per SSD layer the conv (2 x width x
    channels) and the recurrence, 3 x H x P x N to decay the state and add
    Δx ⊗ B and 2 x H x P x N to read it with C; per attention layer 4 x
    heads x head_dim per position attended; 2 x d_model x vocab per LM-head
    row.  The recurrence is counted as such, not as the chunked form."""
    D, F, V = arch.d_model, arch.d_ff, arch.vocab_size
    di, N, H = arch.d_inner_ssd, arch.ssm_state, arch.ssd_heads
    hd, heads_q, kv = arch.resolved_head_dim, arch.num_heads, arch.num_kv_heads
    ssd, attn = _ssd_layers(arch), arch.num_layers - _ssd_layers(arch)
    c = di + 2 * N
    per_ssd = (2 * (D * (di + c + H) + di * D) + 2 * arch.conv_width * c
               + 5 * H * arch.ssd_head_dim * N)
    per_attn = 2 * (D * (heads_q + 2 * kv) * hd + heads_q * hd * D)
    per_token = ssd * per_ssd + attn * per_attn + arch.num_layers * 6 * D * F
    return float(per_token * len(context) + 2.0 * D * V * heads
                 + 4.0 * attn * heads_q * hd * np.sum(context))


def _slice_stats(arch, requests, start, lo, hi) -> dict:
    """Counts over the traced slice [lo, hi] of the host clock: engine loop
    turns, tokens, live positions, model FLOPs, queue waits."""
    stamps, live = [], []
    for r in requests:
        t = np.asarray(r.out_tokens.times)
        inside = np.nonzero((t >= lo) & (t <= hi))[0]
        stamps.append(t[inside])
        # token i > 0 came out of a decode step over prompt + i positions
        live.append(len(r.prompt) + inside[inside > 0])
    stamps = np.concatenate(stamps)
    live = np.concatenate(live)
    turn = steps(np.sort(stamps))
    admitted = [r for r in requests if r.admitted_at is not None
                and lo <= start + r.admitted_at <= hi]
    prompt_ctx = np.concatenate(
        [np.arange(1, len(r.prompt) + 1) for r in admitted] or [np.zeros(0)])
    return {
        "slice_s": hi - lo,
        "slice_tokens": int(len(stamps)),
        "decode_steps": int(turn[-1]) + 1 if len(turn) else 0,
        "live_positions": int(np.sum(live)),
        "kv_bytes_per_position": kv_bytes_per_position(arch),
        "model_flops": (model_flops(arch, live, len(live))
                        + model_flops(arch, prompt_ctx, len(admitted))),
        "queue_ms": [(r.admitted_at - r.arrival) * 1e3 for r in admitted],
    }


def _program_spans(path: str) -> List[trace_reduce.Interval]:
    """The program's ``serve.*`` host spans in a profiler trace."""
    from jax.profiler import ProfileData

    return [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(PROGRAM_SPANS)]


def _span_stats(ctx, device: int) -> dict:
    """From the traced slice: each ``serve.admit`` span's length, and the
    device's idle time by the innermost program span open over it (host
    spans moved onto the device's clock), logged with the longest gaps."""
    path = trace_reduce.find_xplane(ctx.trace_dir)
    spans = _program_spans(path)
    trace = trace_reduce.load(path)
    skew = program_trace.clock_skew_ns(program_trace.load(path), device) or 0
    lo, hi = trace_reduce.window(trace)
    shifted = program_trace.on_device_clock(trace.spans + spans, skew)
    idle = trace_reduce.gaps(trace_reduce.clip(trace.ops.get(device, []), lo,
                                               hi), lo, hi)
    by_span = {k: v * 1e-9 for k, v in
               program_trace.idle_by_span(idle, shifted).items()}
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:10]
    ctx.log("idle by program span (s): " + "; ".join(
        f"{k} {v:.6f}" for k, v in sorted(by_span.items(),
                                          key=lambda kv: -kv[1])))
    ctx.log("longest idle gaps (span, ms): " + "; ".join(
        f"{trace_reduce.span_at(shifted, (s + e) / 2)} {(e - s) * 1e-6:.3f}"
        for s, e in longest))
    return {"admit_ms": [(e - s) * 1e-6 for s, e, name in spans
                         if name == "serve.admit"],
            "idle_s_by_span": by_span}


def run(ctx) -> dict:
    from repro.core import trace

    cfg, mix = ctx.config, ctx.traffic
    engine, params = build_engine(cfg, ctx.seed)
    arch = engine.cfg
    ctx.mark("weights made")
    plan = serving_requests(mix, ctx.seconds, ctx.seed, cfg["vocab_size"])
    engine.warmup(prompt_lens=sorted({len(p["prompt"]) for p in plan}))
    ctx.mark("programs warmed")

    slicer = TraceSlice(ctx)
    on_s, length_s = mix["trace_slice_s"]
    off_s = min(on_s + length_s, ctx.seconds)
    on_s = min(on_s, max(off_s - length_s, 0.0))
    counts, end = {}, [math.inf]

    def stamp(now):
        slicer(now)
        if now >= end[0] and "end" not in counts:
            counts["end"] = trace.snapshot()

    def begin():
        start = ctx.begin_window()
        slicer.arm(start + on_s, start + off_s)
        end[0] = start + ctx.seconds
        counts["begin"] = trace.snapshot()
        return start

    requests, start = serve_window(engine, plan, stamp, begin)
    counts.setdefault("end", trace.snapshot())
    ctx.stop_trace()
    compiles = ctx.compiles_in_window()
    ctx.read_memory_peak()
    ctx.log(f"drain: the last request ended "
            f"{time.perf_counter() - start - ctx.seconds:.3f}s after the "
            f"window")

    window = {k: counts["end"].get(k, 0) - counts["begin"].get(k, 0)
              for k in ("serve.admit_waves", "serve.prefill_tokens",
                        "serve.prefill_pad_tokens")}
    ctx.log(f"window counters: {window}")
    stats = {"slots": engine.batch,
             "prefill_tokens": window["serve.prefill_tokens"],
             "prefill_pad_tokens": window["serve.prefill_pad_tokens"]}
    if slicer.span is not None and slicer.span[1] is not None:
        stats.update(_slice_stats(arch, requests, start, *slicer.span))
        stats["weight_bytes"] = sum(x.nbytes for x in jax.tree.leaves(params))
        stats["state_bytes"] = state_bytes(arch, engine.batch)
        if ctx.trace_dir is not None:
            stats.update(_span_stats(ctx, ctx.devices[0].id))
    check = sample_for_check(requests, ctx.seed, mix["check_requests"])
    t_ref = time.perf_counter()
    widest = float(np.max(reference_gaps(cfg, params, check)))
    ctx.log(f"reference: {sum(len(r.out_tokens) for r in check)} served "
            f"tokens of {len(check)} requests in "
            f"{time.perf_counter() - t_ref:.3f}s")
    return {
        "e2e": window_metrics(requests, start, ctx.seconds),
        "stats": stats,
        "checks": {"served_logit_gap": (
            widest, cfg["limits"]["served_logit_gap"])},
        "attempted": len(requests),
        "failed": sum(1 for r in requests if not r.done),
        "compiles_in_window": compiles,
    }

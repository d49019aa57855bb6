"""Driver for the serving cells: an open-loop request stream through
``ServeEngine.run`` with a ``SlotScheduler``, as a deployment runs it.

Set-up makes the weights on the device from the seed in one jitted call,
in bfloat16, builds the engine, and warms the prefill buckets that this
run's own requests use, the cache scatter and the decode step.  The window
starts with ``engine.run`` over the whole request list; requests arrive at
their scheduled times (``bench/traffic``), and the drain after the window's
last arrival is not timed.

Token times are taken on the client's side: each request's ``out_tokens``
is a list of the benchmark's own that stamps the host clock on every
``append``, and the engine appends each token as soon as the host has it.
So the program is not touched.

Correctness: after the drain, a sample drawn from the seed of the finished
requests, the longest among them, is run through the plain float32 Qwen2
forward (``bench/reference/qwen2.py``) over prompt and served tokens.  At
every served token the reference's best logit minus its logit for the
served token, over the standard deviation of its logits at that position,
is a gap; the widest gap is compared with its limit.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import percentile
from bench.reference import qwen2 as ref
from bench.traffic import serving_requests

# the decode step's tokens of one loop turn are appended within microseconds;
# two turns are a decode step apart, which takes milliseconds
STEP_SEPARATION_S = 1e-3


def arch_config(cfg: Dict, **overrides):
    """The program's ``ArchConfig`` for the configuration file (Hugging
    Face key names), with nothing changed from it."""
    from repro.models.config import ArchConfig

    return ArchConfig(
        name=cfg["name"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        qkv_bias=True, rope_base=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        max_seq_len=cfg["max_position_embeddings"],
        dtype=cfg["torch_dtype"], **overrides)


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def make_params(seed: int, arch, weights: Dict):
    """The served weights, made on the device in one jitted call from the
    seed.  ``weights`` is the configuration's ``weights`` entry: the
    standard deviation of the normals of each leaf by name (``default``
    for the rest), those of the residual-output projections divided by
    sqrt(2 x layers) where ``scale_residual_out`` is set; norm weights are
    exactly 1 (the program stores RMSNorm weights as ``1 + scale``)."""
    from repro.models.transformer import init_model

    from bench.drivers.fit import jax_key

    shapes = jax.eval_shape(lambda k: init_model(k, arch)[0],
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    std = dict(weights["std"])
    if weights.get("scale_residual_out"):
        for name in ("wo", "w_down"):
            std[name] = std.get(name, std["default"]) / math.sqrt(
                2 * arch.num_layers)

    def build(key):
        keys = jax.random.split(key, len(flat))
        leaves = []
        for (path, sds), k in zip(flat, keys):
            name = _leaf_name(path)
            if name == "scale":
                leaves.append(jnp.zeros(sds.shape, sds.dtype))
                continue
            leaves.append((jax.random.normal(k, sds.shape, jnp.float32)
                           * std.get(name, std["default"])).astype(sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.block_until_ready(jax.jit(build)(jax_key(seed)))


class StampedTokens(list):
    """A request's token list that stamps the host clock on each append."""

    def __init__(self, hook):
        super().__init__()
        self.times: List[float] = []
        self._hook = hook

    def append(self, token) -> None:
        now = time.perf_counter()
        super().append(token)
        self.times.append(now)
        self._hook(now)


class TraceSlice:
    """Switches the profiler on and off at fixed times of the window, from
    inside the token stamps (a trace of the whole window would be too large
    to read within a run)."""

    def __init__(self, ctx):
        self.ctx, self.on, self.off = ctx, math.inf, math.inf
        self.span = None

    def arm(self, on: float, off: float) -> None:
        self.on, self.off = on, off

    def __call__(self, now: float) -> None:
        if not self.ctx.trace:
            return
        if self.span is None and now >= self.on:
            self.ctx.start_trace()
            self.span = [time.perf_counter(), None]
        elif self.span is not None and self.span[1] is None and now >= self.off:
            self.span[1] = time.perf_counter()
            self.ctx.stop_trace()


def steps(times: np.ndarray) -> np.ndarray:
    """Index of the engine loop turn each sorted stamp belongs to."""
    if len(times) == 0:
        return np.zeros(0, np.int64)
    return np.concatenate([[0], np.cumsum(np.diff(times) > STEP_SEPARATION_S)])


def model_flops(cfg: Dict, context: np.ndarray, heads: int) -> float:
    """Forward FLOPs of one token at each of the given context lengths,
    ``heads`` of them through the LM head: 2 x the layers' weights per
    token, 4 x layers x heads x head_dim per position attended, and
    2 x d_model x vocab per LM-head row."""
    D, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, L = D // H, cfg["num_hidden_layers"]
    per_layer = D * (H + 2 * KV) * hd + H * hd * D + 3 * D * F
    return float(2.0 * L * per_layer * len(context) + 2.0 * D * V * heads
                 + 4.0 * L * H * hd * np.sum(context))


def kv_bytes_per_position(cfg: Dict) -> int:
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * hd * 2


def layer_view(params, i: int) -> Dict:
    """Layer ``i`` of the program's parameter tree in the reference's plain
    float32 layout."""
    b = params["blocks"]["b0"]
    a, m = b["attn"], b["mlp"]
    f32 = lambda x: x[i].astype(jnp.float32)  # noqa: E731
    flat = lambda x: f32(x).reshape(x.shape[1], -1)  # noqa: E731
    return {
        "ln1": 1.0 + f32(b["ln1"]["scale"]),
        "q_w": flat(a["wq"]), "q_b": f32(a["bq"]).reshape(-1),
        "k_w": flat(a["wk"]), "k_b": f32(a["bk"]).reshape(-1),
        "v_w": flat(a["wv"]), "v_b": f32(a["bv"]).reshape(-1),
        "o_w": f32(a["wo"]).reshape(-1, a["wo"].shape[-1]),
        "ln2": 1.0 + f32(b["ln2"]["scale"]),
        "gate": f32(m["w_gate"]), "up": f32(m["w_up"]),
        "down": f32(m["w_down"]),
    }


def reference_gaps(cfg: Dict, params, requests, chosen=None,
                   chunk: int = 512) -> np.ndarray:
    """The normalised gap, under the float32 reference, of a token at every
    served position of ``requests``: (best logit - the token's logit) / the
    logits' standard deviation.  The token is the served one, or
    ``chosen[i]`` (one id per served token of request i) where given.
    Every sequence is padded to ``max_seq`` and every block of rows to
    ``chunk``, so that the reference compiles one program of each kind."""
    embed = params["embed"]["tok"].astype(jnp.float32)
    final = 1.0 + params["final_norm"]["scale"].astype(jnp.float32)
    length = cfg["serving"]["max_seq"]
    out = []
    for i, r in enumerate(requests):
        served = np.asarray(list(r.out_tokens), np.int32)
        picks = served if chosen is None else np.asarray(chosen[i], np.int32)
        seq = np.concatenate([r.prompt, served[:-1]]).astype(np.int32)
        h = ref.hidden(cfg, lambda i: layer_view(params, i), embed, final,
                       seq, length)
        h = jnp.pad(h, ((0, chunk), (0, 0)))
        first = len(r.prompt) - 1
        for s in range(0, len(served), chunk):
            rows = jax.lax.dynamic_slice_in_dim(h, first + s, chunk)
            block = np.zeros(chunk, np.int32)
            n = min(chunk, len(served) - s)
            block[:n] = picks[s:s + n]
            best, std, pick = ref.logit_rows(rows, embed, jnp.asarray(block))
            out.append(np.asarray((best - pick) / std, np.float64)[:n])
    return np.concatenate(out)


def sample_for_check(requests, seed: int, count: int):
    """The longest finished request and ``count - 1`` others drawn from the
    seed."""
    done = [r for r in requests if r.done and len(r.out_tokens)]
    longest = max(done, key=lambda r: len(r.out_tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 1])
    picks = rng.choice(len(rest), size=min(count - 1, len(rest)),
                       replace=False)
    return [longest] + [rest[i] for i in sorted(picks)]


def build_engine(cfg: Dict, seed: int):
    """The engine over weights made from ``seed``, as the cell serves them,
    and those weights as the benchmark made them: the reference reads
    these, never what the engine keeps."""
    from repro.serve import ServeEngine

    arch = arch_config(cfg)
    params = make_params(seed, arch, cfg["weights"])
    return ServeEngine(arch, params, batch_size=cfg["serving"]["slots"],
                       max_seq=cfg["serving"]["max_seq"]), params


def serve_window(engine, plan, hook, begin):
    """Serve the planned requests through ``engine.run`` with a
    ``SlotScheduler``; ``begin()`` marks the window's start and returns it
    on the host clock.  Returns the requests, their tokens stamped."""
    from repro.serve import Request, SlotScheduler

    requests = [Request(prompt=p["prompt"], max_new_tokens=p["max_new"],
                        arrival=p["arrival"], out_tokens=StampedTokens(hook))
                for p in plan]
    sched = SlotScheduler(engine.batch)
    start = begin()
    with jax.profiler.TraceAnnotation("bench.serve"):
        engine.run(requests, scheduler=sched,
                   now_fn=lambda: time.perf_counter() - start)
    return requests, start


def window_metrics(requests, start: float, seconds: float) -> Dict:
    """The end-to-end serving metrics of a window [start, start + seconds]:
    tokens stamped inside it over its length; the 90th percentile of
    first-token time minus scheduled arrival over every request (one with
    no token counts as missing: infinite), a percentile that leaves at
    least ten requests beyond it in a window of a hundred; the 95th
    percentile of every gap between successive tokens of a request that
    ends inside the window."""
    end = start + seconds
    times = [np.asarray(r.out_tokens.times) for r in requests]
    ttft = [(t[0] - start - r.arrival) * 1e3 if len(t) else math.inf
            for t, r in zip(times, requests)]
    gaps = [g for t in times for g in (np.diff(t)[t[1:] <= end] * 1e3)]
    tokens = sum(int(np.sum(t <= end)) for t in times)
    return {"serve_tokens_per_s": tokens / seconds,
            "ttft_p90_ms": percentile(ttft, 90),
            "itl_p95_ms": percentile(gaps, 95)}


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    engine, params = build_engine(cfg, ctx.seed)
    ctx.mark("weights made")
    plan = serving_requests(mix, ctx.seconds, ctx.seed, cfg["vocab_size"])
    engine.warmup(prompt_lens=sorted({len(p["prompt"]) for p in plan}))
    ctx.mark("programs warmed")

    slicer = TraceSlice(ctx)
    on_s, length_s = mix["trace_slice_s"]
    off_s = min(on_s + length_s, ctx.seconds)
    on_s = min(on_s, max(off_s - length_s, 0.0))

    def begin():
        start = ctx.begin_window()
        slicer.arm(start + on_s, start + off_s)
        return start

    requests, start = serve_window(engine, plan, slicer, begin)
    ctx.stop_trace()
    compiles = ctx.compiles_in_window()
    ctx.read_memory_peak()
    ctx.log(f"drain: the last request ended "
            f"{time.perf_counter() - start - ctx.seconds:.3f}s after the "
            f"window")

    stats = {"slots": engine.batch}
    if slicer.span is not None and slicer.span[1] is not None:
        weight_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
        stats.update(_slice_stats(cfg, requests, start, weight_bytes,
                                  *slicer.span))
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


def _slice_stats(cfg, requests, start, weight_bytes, lo, hi) -> dict:
    """Counts over the traced slice [lo, hi] of the host clock, for the
    per-layer readers: engine loop turns, tokens, live positions, prompt
    tokens admitted, queue waits of the requests admitted."""
    stamps, live = [], []
    for r in requests:
        t = np.asarray(r.out_tokens.times)
        inside = np.nonzero((t >= lo) & (t <= hi))[0]
        stamps.append(t[inside])
        # token i > 0 came out of a decode step over prompt + i positions;
        # token 0 came out of the prefill, counted with the prompts below
        live.append(len(r.prompt) + inside[inside > 0])
    stamps = np.concatenate(stamps)
    live = np.concatenate(live)
    order = np.argsort(stamps)
    turn = steps(stamps[order])
    n_turns = int(turn[-1]) + 1 if len(turn) else 0
    admitted = [r for r in requests if r.admitted_at is not None
                and lo <= start + r.admitted_at <= hi]
    prompt_ctx = np.concatenate(
        [np.arange(1, len(r.prompt) + 1) for r in admitted]
        or [np.zeros(0)])
    return {
        "slice_s": hi - lo,
        "slice_tokens": int(len(stamps)),
        "decode_steps": n_turns,
        "live_positions": int(np.sum(live)),
        "kv_bytes_per_position": kv_bytes_per_position(cfg),
        "weight_bytes": weight_bytes,
        "model_flops": (model_flops(cfg, live, len(live))
                        + model_flops(cfg, prompt_ctx, len(admitted))),
        "queue_ms": [(r.admitted_at - r.arrival) * 1e3 for r in admitted],
    }

"""Multi-pod dry-run: prove every (arch × input-shape × mesh) lowers,
partitions, and compiles — and extract the roofline terms from the compiled
artifact.  No real data is allocated: inputs are ShapeDtypeStructs.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]

Results (memory analysis, FLOPs/bytes from cost_analysis, per-collective
bytes parsed from the post-SPMD HLO) are printed and optionally written as
JSON for EXPERIMENTS.md §Dry-run / §Roofline.
"""
# The VERY FIRST lines, before ANY other import: jax locks the device count
# on first init, and the production mesh needs 512 placeholder host devices.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import re
import time
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint.store import atomic_write_json
from repro.configs import ARCH_IDS, SHAPES, InputShape, get_config
from repro.launch.mesh import make_production_mesh
from repro.models.config import ArchConfig
from repro.models.transformer import TransformerLM, init_model
from repro.optim.optimizers import adamw
from repro.serve.step import cache_axes, make_decode_step, make_prefill_step
from repro.sharding.rules import (DEFAULT_RULES, ShardingRules, logical_to_spec,
                                  shardings_for)
from repro.train.step import (BATCH_AXES, TrainState, init_train_state,
                              make_train_step, state_shardings)

__all__ = ["run_pair", "planned_pairs", "input_specs", "collective_bytes",
           "HW", "main"]

# TPU v5e hardware constants (roofline denominators)
HW = {
    "peak_flops": 197e12,      # bf16 FLOP/s per chip
    "hbm_bw": 819e9,           # bytes/s per chip
    "ici_bw": 50e9,            # bytes/s per link
}

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*(?:e[0-9]+m[0-9]+(?:fn)?)?)\[([0-9,]*)\]")


def _dry_cfg(cfg: ArchConfig, shape: InputShape) -> ArchConfig:
    """Shape-dependent config fixups for the dry-run (position-table sizing)."""
    updates: Dict[str, Any] = {}
    if cfg.max_seq_len < shape.seq_len:
        updates["max_seq_len"] = shape.seq_len
    return dataclasses.replace(cfg, **updates) if updates else cfg


# --------------------------------------------------------------------------- #
# input specs (ShapeDtypeStruct stand-ins; no allocation)
# --------------------------------------------------------------------------- #
def input_specs(cfg: ArchConfig, shape: InputShape) -> Dict[str, jax.ShapeDtypeStruct]:
    """Model-input stand-ins for one (arch, shape) pair."""
    B, S = shape.global_batch, shape.seq_len
    act = jnp.dtype(cfg.dtype)
    if shape.kind == "train":
        specs = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
        if cfg.vision_tokens:
            specs["vision_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.vision_tokens, cfg.d_model), act)
        if cfg.encoder_layers:
            specs["encoder_frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), act)
        return specs
    if shape.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if cfg.vision_tokens:
            specs["vision_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.vision_tokens, cfg.d_model), act)
        if cfg.encoder_layers:
            specs["encoder_frames"] = jax.ShapeDtypeStruct(
                (B, cfg.encoder_seq, cfg.d_model), act)
        return specs
    # decode: ONE new token against a seq_len-deep cache
    return {
        "token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
    }


def _abstract(fn, *args, **kw):
    return jax.eval_shape(fn, *args, **kw)


def _batch_shardings(specs: Dict[str, jax.ShapeDtypeStruct], mesh: Mesh,
                     rules: ShardingRules) -> Dict[str, NamedSharding]:
    return {
        k: NamedSharding(mesh, logical_to_spec(
            BATCH_AXES.get(k, ("batch",) + (None,) * (len(v.shape) - 1)),
            tuple(v.shape), mesh, rules))
        for k, v in specs.items()
    }


# --------------------------------------------------------------------------- #
# lower+compile per shape kind
# --------------------------------------------------------------------------- #
def abstract_train_state(cfg: ArchConfig, optimizer=None):
    """(abstract TrainState, logical axes) with no array allocation.  The
    axes tree is static metadata, captured via a side channel so eval_shape
    only sees array outputs."""
    optimizer = optimizer or adamw()
    box: Dict[str, Any] = {}

    def build(key):
        state, axes = init_train_state(key, cfg, optimizer)
        box["axes"] = axes
        return state

    state_s = jax.eval_shape(build, jax.random.PRNGKey(0))
    return state_s, box["axes"]


def abstract_params(cfg: ArchConfig):
    """(abstract params, logical axes) with no array allocation."""
    box: Dict[str, Any] = {}

    def build(key):
        params, axes = init_model(key, cfg)
        box["axes"] = axes
        return params

    params_s = jax.eval_shape(build, jax.random.PRNGKey(0))
    return params_s, box["axes"]


def _lower_train(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                 rules: ShardingRules):
    optimizer = adamw()
    state_s, axes = abstract_train_state(cfg, optimizer)
    state_sh = state_shardings(state_s, axes, mesh, rules)
    batch = input_specs(cfg, shape)
    batch_sh = _batch_shardings(batch, mesh, rules)
    step = make_train_step(cfg, optimizer, mesh=mesh, rules=rules)
    jitted = jax.jit(step, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None))
    return jitted.lower(state_s, batch)


def _serve_state(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                 rules: ShardingRules):
    """Abstract params + cache and their shardings for serving."""
    params_s, axes = abstract_params(cfg)
    p_sh = shardings_for(axes, params_s, mesh, rules)
    model = TransformerLM(cfg)
    cache_s = _abstract(partial(model.init_cache, shape.global_batch,
                                shape.seq_len))
    c_axes = cache_axes(cfg)
    c_sh = shardings_for(c_axes, cache_s, mesh, rules)
    return params_s, p_sh, cache_s, c_sh


def _lower_prefill(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                   rules: ShardingRules):
    params_s, p_sh, cache_s, c_sh = _serve_state(cfg, shape, mesh, rules)
    specs = input_specs(cfg, shape)
    in_sh = _batch_shardings(specs, mesh, rules)
    prefill = make_prefill_step(cfg)

    def step(params, cache, batch):
        return prefill(params, batch["tokens"], cache,
                       vision_embeds=batch.get("vision_embeds"),
                       encoder_frames=batch.get("encoder_frames"))

    jitted = jax.jit(step, in_shardings=(p_sh, c_sh, in_sh),
                     out_shardings=(None, c_sh))
    return jitted.lower(params_s, cache_s, specs)


def _lower_decode(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                  rules: ShardingRules):
    params_s, p_sh, cache_s, c_sh = _serve_state(cfg, shape, mesh, rules)
    specs = input_specs(cfg, shape)
    tok_sh = NamedSharding(mesh, logical_to_spec(
        ("batch", None), tuple(specs["token"].shape), mesh, rules))
    decode = make_decode_step(cfg)
    jitted = jax.jit(decode,
                     in_shardings=(p_sh, tok_sh, NamedSharding(mesh, P()), c_sh),
                     out_shardings=(None, c_sh))
    return jitted.lower(params_s, specs["token"], specs["pos"], cache_s)


# --------------------------------------------------------------------------- #
# artifact analysis
# --------------------------------------------------------------------------- #
def collective_bytes(hlo: str) -> Dict[str, Any]:
    """Sum result-shape bytes of every collective op in the post-SPMD HLO
    (shapes are per-device after partitioning)."""
    per_op: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo.splitlines():
        stripped = line.strip()
        if "=" not in stripped:
            continue
        for coll in _COLLECTIVES:
            # match the op use (" all-reduce(") not names like %all-reduce.5
            if f" {coll}(" in stripped or f" {coll}-start(" in stripped:
                lhs = stripped.split("=", 1)[1]
                lhs = lhs.split(coll, 1)[0]
                nbytes = 0
                for dt, dims in _SHAPE_RE.findall(lhs):
                    if dt not in _DTYPE_BYTES:
                        continue
                    n = 1
                    for d in dims.split(","):
                        if d:
                            n *= int(d)
                    nbytes += n * _DTYPE_BYTES[dt]
                per_op[coll] += nbytes
                counts[coll] += 1
                break
    total = sum(per_op.values())
    return {"total_bytes": total, "bytes_by_op": per_op, "count_by_op": counts}


def model_flops_estimate(cfg: ArchConfig, shape: InputShape) -> float:
    """MODEL_FLOPS = 6·N_active·D (training) or 2·N_active·D (decode/prefill
    forward-only), N_active = params touched per token."""
    d, L = cfg.d_model, cfg.num_layers
    hd = cfg.resolved_head_dim if cfg.num_heads else 0
    n_per_layer = 0.0
    att_layers = sum(1 for k in cfg.pattern if "attn" in k.value) * cfg.num_periods
    rec_layers = sum(1 for k in cfg.pattern if k.value == "rglru") * cfg.num_periods
    ssd_layers = sum(1 for k in cfg.pattern if k.value == "ssd") * cfg.num_periods
    n = 0.0
    if att_layers:
        n += att_layers * (d * cfg.num_heads * hd + 2 * d * cfg.num_kv_heads * hd
                           + cfg.num_heads * hd * d)
        mlp_per = 3 * d * cfg.d_ff if cfg.mlp_kind == "swiglu" else 2 * d * cfg.d_ff
        if cfg.num_experts:
            active = cfg.top_k + (1 if cfg.shared_expert else 0)
            n += att_layers * active * mlp_per
        else:
            n += att_layers * mlp_per
    if rec_layers:
        r = cfg.rnn_width or d
        n += rec_layers * (3 * d * r + 2 * r * r + r * d + 3 * d * cfg.d_ff)
    if ssd_layers:
        di = cfg.d_inner_ssd
        n += ssd_layers * (2 * d * di + 2 * d * cfg.ssm_state
                           + d * cfg.ssd_heads + di * d)
        if cfg.mlp_kind != "none":      # granite-4.0-h: a SwiGLU after each
            n += ssd_layers * 3 * d * cfg.d_ff
    n += cfg.vocab_size * d  # logits matmul (embeddings tied)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * tokens


def analyze(lowered, compiled) -> Dict[str, Any]:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):  # some backends return [dict]
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    mem: Dict[str, Any] = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                mem[attr] = int(v)
    except Exception as e:  # pragma: no cover
        mem["error"] = str(e)
    coll = collective_bytes(compiled.as_text())
    return {"flops_per_device": flops, "bytes_per_device": bytes_acc,
            "memory": mem, "collectives": coll}


# --------------------------------------------------------------------------- #
# the pair matrix
# --------------------------------------------------------------------------- #
def planned_pairs() -> Tuple[Tuple[str, str], ...]:
    """All (arch, shape) baseline pairs.  long_500k only for sub-quadratic
    archs (skips recorded in DESIGN.md)."""
    out = []
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            if sname == "long_500k" and not cfg.sub_quadratic:
                continue
            if sname == "long_500k" and cfg.encoder_layers > 0:
                continue  # whisper: bounded-source enc-dec
            out.append((arch, sname))
    return tuple(out)


def _lower_kind(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                rules: ShardingRules):
    if shape.kind == "train":
        return _lower_train(cfg, shape, mesh, rules)
    if shape.kind == "prefill":
        return _lower_prefill(cfg, shape, mesh, rules)
    return _lower_decode(cfg, shape, mesh, rules)


def _probe_cfg(cfg: ArchConfig, periods: int) -> ArchConfig:
    """Shallow UNROLLED variant for cost extrapolation: XLA's cost_analysis
    counts a lax.scan body once regardless of trip count, so the scanned
    full-depth program under-reports.  Two unrolled probes (1 and 2 periods)
    give base + per-period body costs; total = base + P·body.  The encoder
    (whisper) stays full-depth in both probes, landing in `base` exactly
    once.  Archs with long periods (recurrentgemma 19, gemma3 13) probe
    with a reduced same-mix ``probe_pattern`` and a fractional period scale.
    Residual known under-count: the q-chunk scan inside one attention
    layer (documented in EXPERIMENTS.md)."""
    pattern = cfg.probe_pattern or cfg.pattern
    return dataclasses.replace(cfg, pattern=pattern, probe_pattern=None,
                               num_layers=periods * len(pattern),
                               unroll_periods=True)


def _extrapolated_costs(cfg: ArchConfig, shape: InputShape, mesh: Mesh,
                        rules: ShardingRules) -> Dict[str, float]:
    probes = []
    for k in (1, 2):
        lowered = _lower_kind(_probe_cfg(cfg, k), shape, mesh, rules)
        probes.append(analyze(lowered, lowered.compile()))
    # effective periods: layers of the full model per probe-pattern length
    P = cfg.num_layers / len(cfg.probe_pattern or cfg.pattern)

    def extrap(c1: float, c2: float) -> float:
        body = max(c2 - c1, 0.0)
        return c1 + (P - 1) * body

    return {
        "flops_per_device": extrap(probes[0]["flops_per_device"],
                                   probes[1]["flops_per_device"]),
        "bytes_per_device": extrap(probes[0]["bytes_per_device"],
                                   probes[1]["bytes_per_device"]),
        "collective_bytes": extrap(probes[0]["collectives"]["total_bytes"],
                                   probes[1]["collectives"]["total_bytes"]),
    }


def run_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
             rules: ShardingRules = DEFAULT_RULES,
             mesh: Optional[Mesh] = None,
             cfg_override: Optional[ArchConfig] = None) -> Dict[str, Any]:
    cfg = _dry_cfg(cfg_override or get_config(arch), SHAPES[shape_name])
    shape = SHAPES[shape_name]
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(mesh.devices.shape))
    t0 = time.time()
    lowered = _lower_kind(cfg, shape, mesh, rules)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    res = analyze(lowered, compiled)
    res["raw_scan_counted"] = {
        "flops_per_device": res["flops_per_device"],
        "bytes_per_device": res["bytes_per_device"],
        "collective_bytes": res["collectives"]["total_bytes"],
    }
    # repair the scan-counted-once under-count via two unrolled probes
    corrected = _extrapolated_costs(cfg, shape, mesh, rules)
    res["flops_per_device"] = corrected["flops_per_device"]
    res["bytes_per_device"] = corrected["bytes_per_device"]
    res["collective_bytes"] = corrected["collective_bytes"]

    res.update({
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    })
    # roofline terms (seconds); flops/bytes are per-device post-SPMD
    res["compute_s"] = res["flops_per_device"] / HW["peak_flops"]
    res["memory_s"] = res["bytes_per_device"] / HW["hbm_bw"]
    res["collective_s"] = res["collective_bytes"] / HW["ici_bw"]
    terms = {k: res[k] for k in ("compute_s", "memory_s", "collective_s")}
    res["bottleneck"] = max(terms, key=terms.get)
    mf = model_flops_estimate(cfg, shape)
    res["model_flops"] = mf
    total_hlo = res["flops_per_device"] * n_chips
    res["model_flops_ratio"] = mf / total_hlo if total_hlo else 0.0
    return res


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--decode-only", action="store_true",
                    help="restrict --all to decode shapes")
    ap.add_argument("--mesh-shape", default=None,
                    help="override mesh, e.g. 32x8 (same 256 chips refactored)")
    ap.add_argument("--serve-rules", action="store_true",
                    help="use SERVE_RULES instead of the training rules")
    ap.add_argument("--out", default=None, help="directory for JSON results")
    args = ap.parse_args()

    pairs = planned_pairs() if args.all else [(args.arch, args.shape)]
    if args.decode_only:
        pairs = [(a, s) for a, s in pairs if SHAPES[s].kind == "decode"]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    override_mesh = None
    if args.mesh_shape:
        shp = tuple(int(x) for x in args.mesh_shape.split("x"))
        names = ("pod", "data", "model")[-len(shp):]
        from repro.core.compat import make_mesh
        override_mesh = make_mesh(shp, names)
    from repro.sharding.rules import SERVE_RULES
    rules = SERVE_RULES if args.serve_rules else DEFAULT_RULES
    for arch, shape in pairs:
        for mp in meshes:
            mesh_tag = args.mesh_shape or ('2x16x16' if mp else '16x16')
            tag = f"{arch}__{shape}__{mesh_tag}"
            try:
                res = run_pair(arch, shape, multi_pod=mp, mesh=override_mesh,
                               rules=rules)
            except Exception as e:  # noqa: BLE001 — report, keep sweeping
                print(f"FAIL {tag}: {type(e).__name__}: {e}")
                if not args.all:
                    raise
                continue
            print(f"OK   {tag}: flops/dev={res['flops_per_device']:.3e} "
                  f"bytes/dev={res['bytes_per_device']:.3e} "
                  f"coll={res['collectives']['total_bytes']:.3e}B "
                  f"bottleneck={res['bottleneck']} "
                  f"(lower {res['lower_s']}s compile {res['compile_s']}s)")
            if args.out:
                # atomic publish: a sweep killed mid-write must not leave a
                # torn result file for the comparison tooling to parse
                atomic_write_json(os.path.join(args.out, tag + ".json"),
                                  res, indent=1)


if __name__ == "__main__":
    main()

"""Hot-path manifest: the registry `python -m repro.analysis audit` runs.

Every entry names one distributed hot path, a zero-arg closure that
traces the *actual* function object the runtime executes (same caches,
same donation flags — not a reconstruction), and the
:class:`~repro.analysis.jaxpr_audit.AuditSpec` it must satisfy.  Tracing
via ``jax.make_jaxpr`` never executes the path, so the audit is cheap,
deterministic, and safe on a CPU CI box.

To register a new hot path::

    @register("subsystem.name", "one-line description")
    def _build():
        fn, args = ...build the jitted callable and example args...
        return AuditTarget(trace=lambda: fn(*args),
                           spec=AuditSpec(expect_donation=("step",)))

Entries that need a real multi-device mesh set ``requires_devices``;
the CLI skips them (with a note) when the process has fewer devices and
``--require-mesh`` turns that skip into a failure (the nightly 8-device
leg runs with it).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

from .finding import Finding
from .jaxpr_audit import AuditSpec, audit_jaxpr

__all__ = [
    "AuditTarget", "HotPath", "register", "hot_paths", "audit_hot_path",
    "run_audit",
]


@dataclasses.dataclass(frozen=True)
class AuditTarget:
    """What one hot path hands the auditor: a zero-arg trace closure
    (``jax.make_jaxpr(trace)()`` must succeed) plus its expectations."""

    trace: Callable[[], Any]
    spec: AuditSpec


@dataclasses.dataclass(frozen=True)
class HotPath:
    name: str
    description: str
    build: Callable[[], AuditTarget]
    requires_devices: int = 1


_REGISTRY: Dict[str, HotPath] = {}


def register(name: str, description: str, *, requires_devices: int = 1):
    """Decorator: register a zero-arg builder as a named hot path."""
    def wrap(build: Callable[[], AuditTarget]) -> Callable[[], AuditTarget]:
        if name in _REGISTRY:
            raise ValueError(f"hot path {name!r} registered twice")
        _REGISTRY[name] = HotPath(name=name, description=description,
                                  build=build,
                                  requires_devices=requires_devices)
        return build
    return wrap


def hot_paths() -> List[HotPath]:
    return [_REGISTRY[name] for name in sorted(_REGISTRY)]


def audit_hot_path(hp: HotPath) -> List[Finding]:
    import jax

    target = hp.build()
    closed = jax.make_jaxpr(target.trace)()
    return audit_jaxpr(closed, target.spec, where=f"hotpath:{hp.name}")


def run_audit(names: Optional[List[str]] = None, *,
              require_mesh: bool = False
              ) -> Tuple[List[Finding], List[str], List[str]]:
    """Audit the registered hot paths.

    Returns ``(findings, audited_names, skipped_names)``.  Paths whose
    ``requires_devices`` exceeds the process device count are skipped
    unless ``require_mesh`` (then a finding is emitted instead).
    """
    import jax

    device_count = len(jax.devices())
    selected = hot_paths()
    if names:
        unknown = sorted(set(names) - set(hp.name for hp in selected))
        if unknown:
            raise KeyError(f"unknown hot path(s): {unknown}")
        selected = [hp for hp in selected if hp.name in set(names)]

    findings: List[Finding] = []
    audited: List[str] = []
    skipped: List[str] = []
    for hp in selected:
        if hp.requires_devices > device_count:
            if require_mesh:
                findings.append(Finding(
                    "audit-skip", f"hotpath:{hp.name}",
                    f"needs {hp.requires_devices} devices, have "
                    f"{device_count} (--require-mesh)"))
            else:
                skipped.append(hp.name)
            continue
        findings.extend(audit_hot_path(hp))
        audited.append(hp.name)
    return findings, audited, skipped


# ---------------------------------------------------------------------------
# shared fixtures (memoized: the audit traces several paths per process)
# ---------------------------------------------------------------------------

_SMOKE: Dict[str, Any] = {}


def _smoke_lm():
    """One tiny transformer + engine reused by every serve entry."""
    if "engine" not in _SMOKE:
        import jax
        from repro.configs import get_smoke
        from repro.models.transformer import init_model
        from repro.serve.engine import ServeEngine

        cfg = get_smoke("qwen2-1.5b")
        params, _ = init_model(jax.random.PRNGKey(0), cfg)
        _SMOKE["cfg"] = cfg
        _SMOKE["params"] = params
        _SMOKE["engine"] = ServeEngine(cfg, params, batch_size=4, max_seq=64)
    return _SMOKE["cfg"], _SMOKE["params"], _SMOKE["engine"]


class _Table:
    """Minimal stand-in for MLNumericTable: the runner only reads .data."""

    def __init__(self, data: Any) -> None:
        self.data = data


def _sgd_step(block, w, r):
    import jax.numpy as jnp

    del r
    resid = block @ w
    return w - 0.01 * (block.T @ resid) / jnp.float32(block.shape[0])


def _mesh_runner(schedule: str):
    import jax
    from repro.core.compat import make_mesh
    from repro.core.runner import DistributedRunner

    mesh = make_mesh((len(jax.devices()),), ("data",))
    return DistributedRunner(mesh=mesh, schedule=schedule, donate=True)


# ---------------------------------------------------------------------------
# runner hot paths (emulated partitions: shape contract is mesh-independent)
# ---------------------------------------------------------------------------

@register("runner.resident_rounds",
          "run_rounds: jitted scan over rounds, donated state carry")
def _build_resident_rounds() -> AuditTarget:
    import jax.numpy as jnp
    from repro.core.runner import DistributedRunner

    runner = DistributedRunner(num_shards=4, donate=True)
    data = jnp.ones((64, 16), jnp.float32)
    w0 = jnp.ones((16, 32), jnp.float32)          # 2 KiB carry
    return AuditTarget(
        trace=lambda: runner.run_rounds(_Table(data), w0, _sgd_step, 3),
        spec=AuditSpec(expect_donation=("mli_rounds",), large_bytes=1 << 10))


@register("runner.streaming_epoch",
          "run_epochs body: one jitted epoch (scan over window chunks), "
          "donated state carry")
def _build_streaming_epoch() -> AuditTarget:
    import jax.numpy as jnp
    from repro.core.runner import DistributedRunner

    runner = DistributedRunner(num_shards=4, donate=True)
    epoch = runner.epoch_fn(_sgd_step, chunks_per_epoch=2)
    window = jnp.ones((64, 16), jnp.float32)
    state = jnp.ones((16, 32), jnp.float32)
    rounds = jnp.arange(2, dtype=jnp.int32)
    return AuditTarget(
        trace=lambda: epoch(state, window, rounds),
        spec=AuditSpec(expect_donation=("epoch",), large_bytes=1 << 10))


@register("runner.stacked_epoch",
          "run_stacked_epochs body: K vmapped trials through one jitted "
          "epoch, traced hyper scalars, donated stacked carry")
def _build_stacked_epoch() -> AuditTarget:
    import jax.numpy as jnp
    from repro.core.optimizer import sgd_trial_round
    from repro.core.runner import DistributedRunner

    runner = DistributedRunner(num_shards=4, donate=True)
    k, d = 4, 16
    step = sgd_trial_round(_grad_row, local_batch_size=16)
    stacked_step, stacked_upd = runner._stacked_fns(step, None)
    epoch = runner.epoch_fn(stacked_step, stacked_upd, chunks_per_epoch=1)
    carry = {
        "trial": jnp.ones((k, d), jnp.float32),
        "hyper": {"lr": jnp.full((k,), 0.05, jnp.float32),
                  "decay": jnp.ones((k,), jnp.float32),
                  "l1": jnp.zeros((k,), jnp.float32)},
        "active": jnp.ones((k,), bool),
        "offset": jnp.zeros((k,), jnp.int32),
    }
    window = jnp.ones((64, d), jnp.float32)
    rounds = jnp.arange(1, dtype=jnp.int32)
    return AuditTarget(
        trace=lambda: epoch(carry, window, rounds),
        spec=AuditSpec(expect_donation=("epoch",), large_bytes=1 << 8))


def _grad_row(vec, w, hyper):
    del hyper
    return (vec @ w) * vec


# ---------------------------------------------------------------------------
# serving hot paths
# ---------------------------------------------------------------------------

@register("serve.fused_decode",
          "ServeEngine._decode: one fused decode step over the shared "
          "slot cache")
def _build_fused_decode() -> AuditTarget:
    import jax.numpy as jnp

    _, params, engine = _smoke_lm()
    cache = engine.init_shared_cache()
    toks = jnp.zeros((engine.batch, 1), jnp.int32)
    pos = jnp.zeros((engine.batch,), jnp.int32)
    return AuditTarget(
        trace=lambda: engine._decode(params, toks, pos, cache),
        spec=AuditSpec())


@register("serve.ragged_prefill",
          "ServeEngine._prefill_ragged: one right-padded mixed-length "
          "admission wave")
def _build_ragged_prefill() -> AuditTarget:
    import jax.numpy as jnp

    _, params, engine = _smoke_lm()
    wb, S = 4, 16
    toks = jnp.zeros((wb, S), jnp.int32)
    lens = jnp.full((wb,), S, jnp.int32)
    return AuditTarget(
        trace=lambda: engine._prefill_ragged(params, toks, lens),
        spec=AuditSpec())


@register("serve.offset_prefill",
          "prefill_ragged(start_pos=): the prefix-cache tail prefill")
def _build_offset_prefill() -> AuditTarget:
    import jax
    import jax.numpy as jnp

    _, params, engine = _smoke_lm()
    wb, S = 2, 8
    # the exact lambda ServeEngine builds when a prefix cache is attached
    fn = jax.jit(lambda p, t, n, s, c: engine.model.prefill_ragged(
        p, t, n, c, start_pos=s))
    sub = engine.model.init_cache(wb, engine.max_seq)
    toks = jnp.zeros((wb, S), jnp.int32)
    lens = jnp.full((wb,), S, jnp.int32)
    starts = jnp.full((wb,), 8, jnp.int32)
    return AuditTarget(
        trace=lambda: fn(params, toks, lens, starts, sub),
        spec=AuditSpec())


@register("serve.span_decode",
          "ReplicaRouter fused span decode: active-lane slice + writeback, "
          "donated fleet cache")
def _build_span_decode() -> AuditTarget:
    import jax.numpy as jnp
    from repro.serve.router import ReplicaRouter

    cfg, params, _ = _smoke_lm()
    router = ReplicaRouter(cfg, params, slots_per_replica=2, max_replicas=2,
                           max_seq=64)
    span = 2
    fn = router._step_for_span(span)
    cache = router.engine.init_shared_cache()
    toks = jnp.zeros((span, 1), jnp.int32)
    pos = jnp.zeros((span,), jnp.int32)
    return AuditTarget(
        trace=lambda: fn(router.engine.params, toks, pos, cache),
        spec=AuditSpec(expect_donation=("step",), large_bytes=1 << 12))


@register("kernels.quant_matmul",
          "int8 quantized matmul wrapper (Pallas on TPU, fp32 dequant "
          "fallback elsewhere)")
def _build_quant_matmul() -> AuditTarget:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    x = jnp.ones((8, 32), jnp.float32)
    w = jnp.ones((32, 16), jnp.float32)

    def path():
        xq, xs = ops.quantize_rows(x)
        wq_t, ws = ops.quantize_rows(w.T)
        return ops.quant_matmul(xq, xs, wq_t.T, ws)

    return AuditTarget(trace=jax.jit(path), spec=AuditSpec())


# ---------------------------------------------------------------------------
# mesh hot paths (real collectives; every CollectiveSchedule lowering)
# ---------------------------------------------------------------------------

@register("mesh.allreduce_round",
          "shard_map round with ALLREDUCE (pmean) combine on the data axis",
          requires_devices=8)
def _build_mesh_allreduce() -> AuditTarget:
    import jax
    import jax.numpy as jnp

    runner = _mesh_runner("allreduce")
    n = len(jax.devices())
    data = jnp.ones((8 * n, 16), jnp.float32)
    w0 = jnp.ones((16, 32), jnp.float32)
    return AuditTarget(
        trace=lambda: runner.run_rounds(_Table(data), w0, _sgd_step, 2),
        spec=AuditSpec(declared_axes=frozenset({"data"}),
                       expect_donation=("mli_rounds",), large_bytes=1 << 10))


@register("mesh.gather_broadcast_epoch",
          "shard_map epoch with GATHER_BROADCAST (all_gather) combine",
          requires_devices=8)
def _build_mesh_gather() -> AuditTarget:
    import jax
    import jax.numpy as jnp

    runner = _mesh_runner("gather_broadcast")
    epoch = runner.epoch_fn(_sgd_step, chunks_per_epoch=1)
    n = len(jax.devices())
    window = jnp.ones((8 * n, 16), jnp.float32)
    state = jnp.ones((16, 32), jnp.float32)
    rounds = jnp.arange(1, dtype=jnp.int32)
    return AuditTarget(
        trace=lambda: epoch(state, window, rounds),
        spec=AuditSpec(declared_axes=frozenset({"data"}),
                       expect_donation=("epoch",), large_bytes=1 << 10))


@register("mesh.reduce_scatter_epoch",
          "shard_map epoch with REDUCE_SCATTER (psum_scatter + all_gather) "
          "combine",
          requires_devices=8)
def _build_mesh_reduce_scatter() -> AuditTarget:
    import jax
    import jax.numpy as jnp

    runner = _mesh_runner("reduce_scatter")
    epoch = runner.epoch_fn(_sgd_step, chunks_per_epoch=1)
    n = len(jax.devices())
    window = jnp.ones((8 * n, 16), jnp.float32)
    state = jnp.ones((16, 32), jnp.float32)
    rounds = jnp.arange(1, dtype=jnp.int32)
    return AuditTarget(
        trace=lambda: epoch(state, window, rounds),
        spec=AuditSpec(declared_axes=frozenset({"data"}),
                       expect_donation=("epoch",), large_bytes=1 << 10))

"""Jaxpr auditor: walk a closed jaxpr and flag distributed-hot-path hazards.

Four rules, each a named invariant the repo's performance claims rest on:

* ``host-callback`` — callback/transfer primitives (``pure_callback``,
  ``io_callback``, ``debug_callback``, ``device_to_host``…) inside a hot
  path force a device→host sync every step: the hidden-straggler class.
* ``f64-promotion`` — a float64 intermediate in a path we compile for
  f32/bf16 doubles bandwidth and silently disables fast matmul paths.
* ``non-donated-carry`` — a jit we *declared* as donating (epoch/step
  carries) whose large operands are all un-donated doubles peak memory.
* ``collective-axis`` — a collective whose axis name is not in the
  declared mesh-axis set for that path: the op would resolve against
  the wrong (or no) mesh and desync the `CollectiveSchedule` contract.

The walker descends into every sub-jaxpr (pjit, scan, while, cond,
shard_map, custom_* …) so nothing hides behind a nested jit.
"""
from __future__ import annotations

import dataclasses
from typing import Any, FrozenSet, Iterator, List, Optional, Tuple

import numpy as np

from .finding import Finding

__all__ = [
    "AuditSpec",
    "audit_jaxpr",
    "iter_eqns",
    "collective_axis_names",
]

# Primitives that imply a host round-trip or callback, matched by the
# name the installed jax registers them under.
CALLBACK_PRIMITIVES = frozenset({
    "pure_callback",
    "io_callback",
    "debug_callback",
    "debug_print",
    "callback",
    "outside_call",        # legacy host_callback
    "host_local_array_to_global_array",
    "device_put",          # explicit placement inside a traced body
    "infeed",
    "outfeed",
})

# Collective primitives that carry a mesh-axis name. NB ``reduce_sum`` /
# ``reduce_max`` etc. are *positional* reductions (their ``axes`` param is
# array dims, not mesh axes) and are deliberately absent.
COLLECTIVE_PRIMITIVES = frozenset({
    "psum",
    "pmin",
    "pmax",
    "pmean",
    "ppermute",
    "pbroadcast",
    "all_gather",
    "all_to_all",
    "psum_scatter",
    "reduce_scatter",
    "axis_index",
})


@dataclasses.dataclass(frozen=True)
class AuditSpec:
    """Expected properties of one hot path's jaxpr.

    Attributes:
      declared_axes: mesh axis names collectives may legally use.  An
        empty set means "this path must use no collectives at all";
        ``None`` disables the collective-axis rule.
      allow_f64: permit float64 intermediates (e.g. a solver path that
        genuinely needs them).
      allow_callbacks: number of callback primitives tolerated (a path
        with a deliberate debug tap can declare it).
      expect_donation: names of inner pjit eqns (``jax.jit``'d function
        names) that must donate at least one large operand.
      large_bytes: threshold above which an operand counts as "large"
        for the donation rule.
    """

    declared_axes: Optional[FrozenSet[str]] = frozenset()
    allow_f64: bool = False
    allow_callbacks: int = 0
    expect_donation: Tuple[str, ...] = ()
    large_bytes: int = 1 << 14


def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """Yield every eqn in ``jaxpr`` and, recursively, all sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _sub_jaxprs(params: dict) -> Iterator[Any]:
    """Find nested jaxprs inside an eqn's params (pjit/scan/cond/...)."""
    for value in params.values():
        for sub in _as_jaxprs(value):
            yield sub


def _as_jaxprs(value: Any) -> Iterator[Any]:
    # ClosedJaxpr has .jaxpr; raw Jaxpr has .eqns. Branch params (cond)
    # are tuples of ClosedJaxprs.
    if hasattr(value, "jaxpr"):
        yield value.jaxpr
    elif hasattr(value, "eqns"):
        yield value
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from _as_jaxprs(item)


def collective_axis_names(eqn: Any) -> Tuple[str, ...]:
    """Extract mesh-axis names used by a collective eqn."""
    names: List[str] = []
    for key in ("axis_name", "axes"):
        value = eqn.params.get(key)
        if value is None:
            continue
        if isinstance(value, str):
            names.append(value)
        elif isinstance(value, (tuple, list)):
            names.extend(v for v in value if isinstance(v, str))
    return tuple(names)


def _aval_bytes(aval: Any) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize
    except (TypeError, ValueError):  # abstract / polymorphic dims
        return 0


def _is_f64(aval: Any) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and np.dtype(dtype) == np.float64


def audit_jaxpr(closed: Any, spec: AuditSpec, *, where: str) -> List[Finding]:
    """Audit one closed jaxpr against ``spec``; return findings."""
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    findings: List[Finding] = []

    callbacks: List[str] = []
    f64_hits: List[str] = []
    donating_seen: dict = {name: None for name in spec.expect_donation}

    for eqn in iter_eqns(jaxpr):
        prim = eqn.primitive.name

        if prim in CALLBACK_PRIMITIVES:
            # a device_put with no target devices is the tracer staging a
            # host constant (e.g. jnp.asarray of a numpy array) — aliasing,
            # not a transfer; one WITH a target sharding is real placement
            # leaked into the traced body, which we do flag
            devices = eqn.params.get("devices") if prim == "device_put" else None
            if not (prim == "device_put"
                    and devices is not None
                    and all(d is None for d in devices)):
                callbacks.append(prim)

        if not spec.allow_f64:
            for var in eqn.outvars:
                if _is_f64(getattr(var, "aval", None)):
                    f64_hits.append(f"{prim} -> {var.aval.str_short()}")
                    break

        if spec.declared_axes is not None and prim in COLLECTIVE_PRIMITIVES:
            for axis in collective_axis_names(eqn):
                if axis not in spec.declared_axes:
                    declared = sorted(spec.declared_axes) or ["<none>"]
                    findings.append(Finding(
                        "collective-axis", where,
                        f"collective '{prim}' uses axis {axis!r} but this "
                        f"path declares axes {declared}: the op would bind "
                        f"to an undeclared mesh axis."))

        if prim == "jit" and eqn.params.get("name") in donating_seen:
            donating_seen[eqn.params["name"]] = eqn

    if len(callbacks) > spec.allow_callbacks:
        findings.append(Finding(
            "host-callback", where,
            f"{len(callbacks)} host callback/transfer primitive(s) "
            f"({', '.join(sorted(set(callbacks)))}) in a hot path "
            f"(allowed {spec.allow_callbacks}): each one forces a "
            f"device->host sync per step."))

    if f64_hits:
        findings.append(Finding(
            "f64-promotion", where,
            f"float64 intermediate(s) in an f32/bf16 path, e.g. "
            f"{f64_hits[0]}: doubles bandwidth and disables fast matmul."))

    for name, eqn in donating_seen.items():
        if eqn is None:
            findings.append(Finding(
                "non-donated-carry", where,
                f"expected a donating jit named {name!r} but no such pjit "
                f"eqn exists in this jaxpr."))
            continue
        donated = eqn.params.get("donated_invars", ())
        large = [v for v in eqn.invars
                 if _aval_bytes(getattr(v, "aval", None)) >= spec.large_bytes]
        if large and not any(donated):
            sizes = ", ".join(v.aval.str_short() for v in large[:3])
            findings.append(Finding(
                "non-donated-carry", where,
                f"jit {name!r} carries large operand(s) [{sizes}] with no "
                f"donated buffers: peak memory doubles on every step."))

    return findings

"""The program's instrumentation: named host spans on the profiler's clock,
process-wide counters, and the one ``jax.monitoring`` listener that turns
JAX's own compile events into counters.

* :func:`span` is a ``jax.profiler.TraceAnnotation``.  While a profiler
  trace runs it lands in the profiler's host plane, on the clock of XLA's
  own host events (``DoEnqueueProgram``); a span's parent is the span open
  around it on the same thread.  Under a JAX trace (inside ``jit`` or
  ``make_jaxpr``) it opens nothing: a span times host work, and host code
  run while tracing is not the work a call does.
* :func:`count` and :func:`snapshot` keep process-wide counters; a reader
  takes two snapshots and subtracts.
* One listener for JAX's duration events and one for its plain events,
  each registered once per process when this module is imported, count:

  ``jax.traces``, ``jax.trace_s``: ``/jax/core/compile/jaxpr_trace_duration``;
  ``jax.lowerings``, ``jax.lower_s``:
  ``/jax/core/compile/jaxpr_to_mlir_module_duration``;
  ``jax.builds``, ``jax.build_s``:
  ``/jax/core/compile/backend_compile_duration``;
  ``jax.cache_loads``: ``/jax/compilation_cache/cache_hits``.

  A build is one ``compile_or_get_cached`` call: an XLA compile, or a load
  of the executable from the persistent compilation cache (which also
  counts a ``jax.cache_loads``).  A call that hits jit's in-memory cache
  emits none of these events.

The program's own counters are ``runner.launches``, ``runner.rounds`` and
``runner.launch_s`` (:meth:`repro.core.runner.DistributedRunner.run_rounds`).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict

import jax

__all__ = ["count", "snapshot", "span"]

# JAX event -> (counter of events, counter of their seconds)
_DURATION_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("jax.traces", "jax.trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("jax.lowerings",
                                                        "jax.lower_s"),
    "/jax/core/compile/backend_compile_duration": ("jax.builds",
                                                   "jax.build_s"),
}
_EVENTS = {"/jax/compilation_cache/cache_hits": "jax.cache_loads"}

_lock = threading.Lock()
_counts: Dict[str, float] = {}


def span(name: str):
    """A host span named ``name`` for a ``with`` block."""
    if not jax.core.trace_ctx.is_top_level():
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    with _lock:
        _counts[name] = _counts.get(name, 0) + n


def snapshot() -> Dict[str, float]:
    """Every counter's value now."""
    with _lock:
        return dict(_counts)


def _on_duration(event: str, duration_secs: float, **_) -> None:
    names = _DURATION_EVENTS.get(event)
    if names is not None:
        with _lock:
            _counts[names[0]] = _counts.get(names[0], 0) + 1
            _counts[names[1]] = _counts.get(names[1], 0) + duration_secs


def _on_event(event: str, **_) -> None:
    name = _EVENTS.get(event)
    if name is not None:
        count(name)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)

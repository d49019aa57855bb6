"""Retrace sentinel: turn "a warmed hot loop never recompiles" into an assert.

jax emits ``/jax/core/compile/backend_compile_duration`` once for every
program it builds: each call of ``compile_or_get_cached``, which is an XLA
compile or a load of the executable from the persistent compilation cache.
Only a call that hits a jit's in-memory cache emits nothing.  So a fresh
``jax.jit`` of a function already in the persistent cache still counts as a
build: inside a warmed loop it is the same miss, a program built again.
The event is counted as ``jax.builds`` by the program's one listener
(:mod:`repro.core.trace`); this module exposes:

* :func:`watch_compiles` — context manager yielding a :class:`CompileWatch`
  whose ``.compiles`` counts program builds inside the block.
* :func:`assert_no_retrace` — context manager that raises
  :class:`RetraceError` if more than ``allow`` builds happen inside the
  block.  This is the asserted form of the PR-3 "one compiled epoch
  serves the whole search" and PR-8 "a fixed fleet never compiles
  mid-stream" claims.

Builds on every thread count, as the listener sees them all.
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional

from repro.core import trace

__all__ = [
    "CompileWatch",
    "RetraceError",
    "assert_no_retrace",
    "watch_compiles",
]


class RetraceError(AssertionError):
    """A region that promised zero recompiles compiled anyway."""


def _builds() -> float:
    return trace.snapshot().get("jax.builds", 0)


class CompileWatch:
    """Counts program builds observed while the watch is active.  (A
    single user-visible trace may produce several — ``jnp.ones`` builds its
    own fill program — which is what a warmed loop must not do.)"""

    def __init__(self) -> None:
        self._start = _builds()
        self._end: Optional[float] = None

    @property
    def compiles(self) -> int:
        end = _builds() if self._end is None else self._end
        return int(end - self._start)

    def _close(self) -> None:
        self._end = _builds()


@contextlib.contextmanager
def watch_compiles() -> Iterator[CompileWatch]:
    """Count jax program builds that happen inside the ``with`` block."""
    watch = CompileWatch()
    try:
        yield watch
    finally:
        watch._close()


@contextlib.contextmanager
def assert_no_retrace(what: str = "", *, allow: int = 0) -> Iterator[CompileWatch]:
    """Fail with :class:`RetraceError` if the block compiles anything.

    Args:
      what: label for the guarded region, used in the error message.
      allow: number of compiles to tolerate (default 0 — fully warmed).

    Usage::

        router.warmup(prompt_lens=[16, 64])
        with assert_no_retrace("fleet serve after warmup"):
            router.run(requests)
    """
    with watch_compiles() as inner:
        yield inner
    count = inner.compiles
    if count > allow:
        label = f" in {what!r}" if what else ""
        raise RetraceError(
            f"expected at most {allow} jax compile(s){label}, observed "
            f"{count}: a warmed hot loop retraced.  Look for shape drift, "
            f"weak-type promotion, non-hashable static args, or a jit "
            f"constructed inside the loop."
        )

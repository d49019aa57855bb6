"""The program's own host spans in a profiler trace (``*.xplane.pb``), put
on the device's clock, and the device's idle time named by them.

The program opens host spans named ``mli.*`` (``repro.core.trace``:
``mli.fit``, and inside it ``mli.runner.prepare`` and
``mli.runner.launch``).  ``bench/trace_reduce.py`` loads only the
benchmark's ``bench.*`` spans and reads the device plane's clock as the
host plane's; this module adds what naming an idle gap by the program's
spans needs:

* the ``mli.*`` spans, from the host planes;
* the clock skew: each device program starts after the host's
  ``DoEnqueueProgram`` event that launched it began (the two are paired by
  ``run_id`` and ``device_ordinal``), so the largest (enqueue start - program
  start) over one device's programs is the least the device clock can lag
  the host's.  Host spans shifted back by it lie on the device's clock.  On
  the recorded v5e trace (``tests/bench/data/v5e_tiny.xplane.pb``) every
  program starts 1.198-1.244 ms before its enqueue: 1.244 ms of skew;
* the device-idle time of a window under each innermost span.

``summarize`` gives those as keys to merge into ``trace_reduce.summarize``'s
result; the readers ``bench/metrics/fit.launch_ms_per_fit.py`` and
``fit.rounds_program_ms_per_round.py`` read the merged result.  Everything
past ``load`` works on plain ``(start_ns, end_ns, name)`` tuples.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce as tr
from bench.trace_reduce import Interval

PROGRAM_SPAN_PREFIX = "mli."
ENQUEUE = "DoEnqueueProgram"
NO_SPAN = "no span"                  # trace_reduce.span_at's name for none


@dataclasses.dataclass
class ProgramTrace:
    """The program's spans (host clock) and, per device, host enqueue
    start minus device start of each program run, in ns."""
    spans: List[Interval]
    lags: Dict[int, List[float]]


def load(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    spans: List[Interval] = []
    starts: Dict[Tuple[int, int], float] = {}      # (device, run_id) -> ns
    enqueues: Dict[Tuple[int, int], float] = {}    # (ordinal, run_id) -> ns
    for plane in data.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Modules":
                    for ev in line.events:
                        run_id = dict(ev.stats).get("run_id")
                        if run_id is not None:
                            starts[dev, int(run_id)] = ev.start_ns
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PROGRAM_SPAN_PREFIX):
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
                    elif ev.name == ENQUEUE:
                        stats = dict(ev.stats)
                        if "run_id" in stats and "device_ordinal" in stats:
                            enqueues[int(stats["device_ordinal"]),
                                     int(stats["run_id"])] = ev.start_ns
    lags: Dict[int, List[float]] = {}
    for key, start in starts.items():
        if key in enqueues:
            lags.setdefault(key[0], []).append(enqueues[key] - start)
    return ProgramTrace(spans=spans, lags=lags)


def clock_skew_ns(program: ProgramTrace, device: int) -> Optional[float]:
    """How far the device's clock lags the host's, at least: the largest
    enqueue-start minus program-start over the device's runs; ``None``
    where no run could be paired with its enqueue."""
    lags = program.lags.get(device)
    return max(lags) if lags else None


def on_device_clock(spans: Sequence[Interval], skew_ns: float
                    ) -> List[Interval]:
    return [(s - skew_ns, e - skew_ns, name) for s, e, name in spans]


def innermost(spans: Sequence[Interval]) -> List[Interval]:
    """The time the spans cover, cut at every span edge, each piece named by
    the innermost span open over it."""
    edges = sorted({t for s, e, _ in spans for t in (s, e)})
    out = []
    for a, b in zip(edges, edges[1:]):
        name = tr.span_at(spans, (a + b) / 2)
        if name != NO_SPAN:
            out.append((a, b, name))
    return out


def idle_by_span(idle: Sequence[Tuple[float, float]],
                 spans: Sequence[Interval]) -> Dict[str, float]:
    """ns of the sorted, disjoint ``idle`` stretches under each innermost
    span; what no span covers is under ``"no span"``."""
    pieces = innermost(spans)
    out: Dict[str, float] = {}
    j = 0
    for gs, ge in idle:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        covered = 0.0
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            s, e, name = pieces[k]
            part = min(e, ge) - max(s, gs)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            k += 1
        if ge - gs > covered:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (ge - gs - covered)
    return out


def summarize(trace: tr.Trace, program: ProgramTrace,
              devices: Sequence[int], top: int = 10) -> dict:
    """The keys this module adds to ``trace_reduce.summarize``'s result.

    ``spans`` holds the benchmark's and the program's spans on the host
    clock (readers shift them by ``clock_skew_ns``); ``idle_gaps`` names the
    longest gaps on the first device by the innermost span open at their
    midpoint, spans shifted to the device's clock; ``idle_s_by_span`` is the
    window's device-idle seconds under each innermost span."""
    lo, hi = tr.window(trace)
    first = devices[0]
    skew = clock_skew_ns(program, first)
    spans = list(trace.spans) + program.spans
    shifted = on_device_clock(spans, skew or 0.0)
    idle = tr.gaps(tr.clip(trace.ops.get(first, []), lo, hi), lo, hi)
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    return {
        "spans": spans,
        "clock_skew_ns": skew,
        "idle_gaps": [[tr.span_at(shifted, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in longest],
        "idle_s_by_span": {name: ns * 1e-9 for name, ns in
                           idle_by_span(idle, shifted).items()},
    }

"""Reduce a profiler trace (``*.xplane.pb``) to the numbers the benchmark's
per-layer metrics read: the union of device-busy intervals, device time by
operation name, collective time, and the idle gaps, each named by the
benchmark's own host span that was open around it.

Only ``jax.profiler.ProfileData`` is used to read the file; everything past
``load`` works on plain ``(start_ns, end_ns, name)`` tuples so that it can be
checked without a trace.  Device planes are those named ``/device:TPU:<n>``;
their ``XLA Ops`` line holds one event per executed operation and their
``XLA Modules`` line one per executed program.  Host spans are the events
whose names start with ``bench.`` (``jax.profiler.TraceAnnotation`` entered
by the benchmark's drivers), on any host thread.  On a v5e the device
plane's clock was seen 1-2 ms behind the host plane's; nothing here
corrects for it, which moves a reading over a window of seconds by a
thousandth at most.  Op names are shortened to the HLO instruction's name
and opcode (``%fusion.2 fusion``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]          # (start_ns, end_ns, name)

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# "%copy.5 = f32[1,4]{1,0:T(4,128)} copy(f32[...] %x), ..." -> "%copy.5 copy"
HLO_OP = re.compile(r"^(%\S+) = .*?\s([a-z][\w-]*)\(")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# XLA's names for the collectives it emits on TPU (async pairs included:
# all-gather-start / -done and the like carry the same stem)
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")


@dataclasses.dataclass
class Trace:
    """What one profile holds, as plain intervals.  ``ops`` and ``modules``
    are keyed by device index."""
    ops: Dict[int, List[Interval]]
    modules: Dict[int, List[Interval]]
    spans: List[Interval]


def short_name(name: str) -> str:
    m = HLO_OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[int, List[Interval]] = {}
    modules: Dict[int, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    target = ops.setdefault(dev, [])
                elif line.name == "XLA Modules":
                    target = modules.setdefault(dev, [])
                else:
                    continue
                target.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                               short_name(ev.name)) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name) for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, modules=modules, spans=spans)


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of ``intervals`` inside [lo, hi]."""
    out = []
    for s, e, name in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e, name))
    return out


def union(intervals: Sequence[Interval]) -> List[Tuple[float, float]]:
    """Merged, sorted, non-overlapping (start, end) pairs."""
    merged: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals: Sequence[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def self_time_by_name(intervals: Sequence[Interval]) -> Dict[str, float]:
    """Nanoseconds per operation name, each event counted without the
    events nested inside it (a loop op that spans its body ops would
    otherwise count their time twice)."""
    totals: Dict[str, float] = {}
    stack: List[List] = []             # [end, name, child_ns, start]

    def pop():
        end, name, child, start = stack.pop()
        totals[name] = totals.get(name, 0.0) + (end - start) - child

    for s, e, name in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        while stack and s >= stack[-1][0]:
            pop()
        if stack:
            stack[-1][2] += e - s
        stack.append([e, name, 0.0, s])
    while stack:
        pop()
    return totals


def gaps(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle stretches of [lo, hi]: no device operation running."""
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def span_at(spans: Sequence[Interval], t: float) -> str:
    """Name of the innermost (shortest) host span open at ``t``."""
    best: Optional[Interval] = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no span"


def window(trace: Trace) -> Tuple[float, float]:
    """[start, end] of the benchmark's traced window, from its host span."""
    ws = [iv for iv in trace.spans if iv[2] == WINDOW_SPAN]
    if not ws:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    return ws[0][0], ws[0][1]


def summarize(trace: Trace, devices: Sequence[int], top: int = 10) -> dict:
    """Everything the readers and the result's ``breakdown`` take from one
    trace, in seconds, over the traced window and the given devices.

    ``busy_s`` is the union of op intervals averaged over ``devices``;
    ``op_s`` is self time by op name summed over them; ``collective_s`` the
    collective ops' self time on the first device; ``module_s`` device time
    by program name, summed over devices; ``idle_gaps`` the longest gaps on
    the first device, each named by the host span open at its midpoint."""
    lo, hi = window(trace)
    busy = [busy_ns(trace.ops.get(d, []), lo, hi) for d in devices]
    op_s: Dict[str, float] = {}
    module_s: Dict[str, float] = {}
    module_n: Dict[str, int] = {}
    for d in devices:
        for name, ns in self_time_by_name(
                clip(trace.ops.get(d, []), lo, hi)).items():
            op_s[name] = op_s.get(name, 0.0) + ns * 1e-9
        for s, e, name in clip(trace.modules.get(d, []), lo, hi):
            module_s[name] = module_s.get(name, 0.0) + (e - s) * 1e-9
            module_n[name] = module_n.get(name, 0) + 1
    first = devices[0]
    first_ops = clip(trace.ops.get(first, []), lo, hi)
    collective = sum(ns for name, ns in self_time_by_name(first_ops).items()
                     if COLLECTIVE.search(name)) * 1e-9
    idle = sorted(gaps(first_ops, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "op_s": op_s,
        "module_s": module_s,
        "module_n": module_n,
        "collective_s": collective,
        "device_ops": sorted(([n, s] for n, s in op_s.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[span_at(trace.spans, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in idle],
        "spans": trace.spans,
        "ops_first": first_ops,
    }

"""The benchmark's harness: finds a cell and everything it names by file,
checks the device, hands the run to the cell's driver, and prints the one
result line.

Everything that belongs to one cell, configuration, traffic mix, driver or
per-layer metric sits in a file of its own, found by name:

    BENCHMARK.json                   the cells, metrics and bounds
    bench/workloads/<cell>.json      {"config", "traffic", "chips", "driver"}
    bench/configs/<config>.json      the configuration's sizes (BENCHMARK.json
                                     names the file)
    bench/traffic/<traffic>.json     the traffic mix's parameters
    bench/drivers/<driver>.py        ``run(ctx) -> dict``: set-up, window,
                                     correctness check
    bench/metrics/<metric>.py        ``read(run) -> float | None``: one
                                     per-layer metric, from the trace and the
                                     driver's own counts and stamps

A later cell, mix, driver or metric is a new file and a new entry; nothing
here changes.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time
import types
from typing import Dict, List, Optional, Sequence

ROOT = pathlib.Path(__file__).resolve().parents[1]


class Refused(Exception):
    """The run cannot be made here: no chip, too few chips, a device the
    peak table does not know, or a cell that its files do not describe."""


# --------------------------------------------------------------------------- #
# discovery
# --------------------------------------------------------------------------- #
def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str) -> types.ModuleType:
    if not path.is_file():
        raise Refused(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    driver: str
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric["workloads"] if "workloads" in metric else True


def find_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` as BENCHMARK.json lists it, with its cell, config
    and traffic files read, and the metrics it reports."""
    spec = load_json(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in spec["workloads"]}
    if name not in entries:
        raise Refused(f"BENCHMARK.json lists no workload {name!r}; it has "
                      f"{sorted(entries)}")
    entry = entries[name]
    cell = load_json(root / "bench" / "workloads" / f"{name}.json")
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise Refused(f"bench/workloads/{name}.json says {key}="
                          f"{cell[key]!r}, BENCHMARK.json says {entry[key]!r}")
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[entry["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{entry['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _reports(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return Cell(name=name, config_name=entry["config"],
                traffic_name=entry["traffic"], chips=int(entry["chips"]),
                driver=cell["driver"], config=config, traffic=traffic,
                end_to_end=e2e, per_layer=layer)


def load_driver(name: str, root: pathlib.Path = ROOT) -> types.ModuleType:
    return load_module(root / "bench" / "drivers" / f"{name}.py",
                       f"bench_driver_{name}")


def load_metric(name: str, root: pathlib.Path = ROOT) -> types.ModuleType:
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       f"bench_metric_{name.replace('.', '_')}")


# --------------------------------------------------------------------------- #
# device
# --------------------------------------------------------------------------- #
def require_devices(chips: int, peaks: dict, devices: Sequence) -> Sequence:
    """The first ``chips`` devices, if they are TPUs the peak table knows;
    otherwise refuse (no fallback to another platform)."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "nothing"
        raise Refused(f"needs a TPU; JAX found {found!r}")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise Refused(f"device_kind {kind!r} is not in bench/peaks.json "
                      f"({sorted(peaks)})")
    return devices[:chips]


class CompileMeter:
    """Persistent-cache hits and misses (a miss is a compile), counted from
    ``jax.monitoring`` events."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"cache_hits": self.hits, "cache_misses": self.misses}


def memory_peak_bytes(devices: Sequence) -> Optional[int]:
    """Peak bytes in use on the fullest of ``devices`` so far."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# --------------------------------------------------------------------------- #
# the context a driver runs in
# --------------------------------------------------------------------------- #
class Context:
    """What a driver gets: the cell's files, the run's arguments, the
    devices, and the calls that mark the window, switch the profiler and
    read the memory peak."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool,
                 devices: Sequence, t_start: float, meter: CompileMeter):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.chips = cell.chips
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = list(devices)
        self.meter = meter
        self.t_start = t_start
        self.setup_s: Optional[float] = None
        self.memory_peak: Optional[int] = None
        self.trace_dir: Optional[str] = None
        self._annotation = None
        self.log = lambda msg: print(msg, file=sys.stderr, flush=True)

    def mark(self, what: str) -> None:
        """Log how far into the run (from process start) a set-up step
        ended, so that a slow set-up can be told apart by its steps."""
        self.log(f"set-up: {what} at {time.perf_counter() - self.t_start:.3f}s")

    def begin_window(self) -> float:
        """Set-up ends here; returns the window's start on the host clock."""
        now = time.perf_counter()
        self.setup_s = now - self.t_start
        self.compiles_at_window = self.meter.snapshot()
        return now

    def compiles_in_window(self) -> dict:
        now = self.meter.snapshot()
        return {k: now[k] - self.compiles_at_window[k] for k in now}

    def start_trace(self) -> None:
        """Start the profiler (only in a ``--trace 1`` run) and open the
        ``bench.window`` host span that marks the traced window."""
        if not self.trace or self.trace_dir is not None:
            return
        import jax

        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._annotation = jax.profiler.TraceAnnotation("bench.window")
        self._annotation.__enter__()

    def stop_trace(self) -> None:
        if self._annotation is None:
            return
        import jax

        self._annotation.__exit__(None, None, None)
        self._annotation = None
        jax.profiler.stop_trace()

    def read_memory_peak(self) -> None:
        self.memory_peak = memory_peak_bytes(self.devices)


# --------------------------------------------------------------------------- #
# arithmetic the metrics share
# --------------------------------------------------------------------------- #
def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` % of
    the values at or below it.  Missing samples are passed as ``inf``, so a
    failed request counts against the tail."""
    if not values:
        return math.nan
    ordered = sorted(values)
    k = max(math.ceil(q / 100.0 * len(ordered)) - 1, 0)
    return float(ordered[k])


# --------------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------------- #
def _finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def _metric_entry(defn: dict, value: float) -> dict:
    return {"value": value, "unit": defn["unit"]}


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             root: pathlib.Path = ROOT) -> dict:
    """Run ``cell`` once and return the result line as a dict (the checks
    last)."""
    import jax

    peaks = load_json(root / "bench" / "peaks.json")
    devices = jax.devices()
    if require_tpu:
        devices = require_devices(cell.chips, peaks, devices)
        from repro.launch.compile_cache import enable_compile_cache

        # run.py names the directory; every program goes into it, however
        # quickly it compiled, so that a second run compiles nothing
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    else:
        devices = devices[:cell.chips]
    dev = devices[0]
    meter = CompileMeter()
    ctx = Context(cell, seed, seconds, trace, devices, t_start, meter)
    ctx.mark("devices found")
    driver = load_driver(cell.driver, root)
    try:
        out = driver.run(ctx)
    finally:
        ctx.stop_trace()
    compiles = out.get("compiles_in_window", {})
    ctx.log(f"inside the window: {compiles.get('cache_misses')} programs "
            f"compiled, {compiles.get('cache_hits')} loaded from the "
            f"persistent cache")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": ctx.memory_peak}
    result = {"correct": None, "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": {}, "device": device}
    e2e = dict(out["e2e"], setup_s=ctx.setup_s)
    if trace:
        from bench import trace_reduce

        summary = None
        if ctx.trace_dir is not None:
            try:
                summary = trace_reduce.summarize(
                    trace_reduce.load(trace_reduce.find_xplane(ctx.trace_dir)),
                    [d.id for d in devices])
            finally:
                shutil.rmtree(ctx.trace_dir, ignore_errors=True)
            top = sorted(summary["module_s"].items(), key=lambda kv: -kv[1])
            ctx.log("device programs in the traced window (name, runs, s): "
                    + "; ".join(f"{n} {summary['module_n'][n]} {s:.6f}"
                                for n, s in top[:8]))
        view = types.SimpleNamespace(
            trace=summary, stats=out.get("stats", {}), e2e=e2e,
            config=cell.config, traffic=cell.traffic, chips=cell.chips,
            peaks=peaks.get(dev.device_kind, {}))
        for defn in cell.per_layer:
            value = load_metric(defn["name"], root).read(view)
            if value is not None:
                result["metrics"][defn["name"]] = _metric_entry(defn, value)
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
    else:
        for defn in cell.end_to_end:
            result["metrics"][defn["name"]] = _metric_entry(
                defn, e2e[defn["name"]])
    checks = {name: {"value": v, "limit": lim}
              for name, (v, lim) in out["checks"].items()}
    result["correct"] = bool(
        checks and result["failed"] == 0
        and all(_finite(c["value"]) and c["value"] <= c["limit"]
                for c in checks.values()))
    result["checks"] = checks
    return result


def main(argv: Optional[List[str]] = None, t_start: Optional[float] = None
         ) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = find_cell(args.workload)
        result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=t_start)
    except Refused as e:
        print(f"bench: refused: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0

"""Runner (host): device-idle time inside the runner's launch of the
rounds program, in ms per fit.  Each ``mli.runner.launch`` host span of
the traced window (retrace, lowering, persistent-cache load, enqueue),
shifted onto the device's clock by ``clock_skew_ns``, minus the device-busy
time inside it, averaged over those spans: one per fit."""
from bench import trace_reduce


def read(run):
    if run.trace is None or run.trace.get("clock_skew_ns") is None:
        return None
    skew = run.trace["clock_skew_ns"]
    launches = [(s - skew, e - skew) for s, e, name in run.trace["spans"]
                if name == "mli.runner.launch"]
    if not launches:
        return None
    ops = run.trace["ops_first"]
    idle_ns = [(e - s) - trace_reduce.busy_ns(ops, s, e) for s, e in launches]
    return sum(idle_ns) / len(idle_ns) * 1e-6

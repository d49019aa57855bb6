"""Estimator API: host time per ``fit()`` that the device spends idle,
in ms.  Each ``bench.fit`` host span minus the device-busy time inside it,
averaged over the fits of the traced window."""
from bench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    fits = [s for s in run.trace["spans"] if s[2] == "bench.fit"]
    if not fits:
        return None
    ops = run.trace["ops_first"]
    idle_ns = [(e - s) - trace_reduce.busy_ns(ops, s, e) for s, e, _ in fits]
    return sum(idle_ns) / len(idle_ns) * 1e-6

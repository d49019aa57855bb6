"""Collectives: share of the traced window that collective operations
(all-gather, all-reduce, reduce-scatter, ...) took on the first chip, in
%.  Nothing to read where the trace holds no collective."""


def read(run):
    if run.trace is None or run.trace["collective_s"] <= 0:
        return None
    return run.trace["collective_s"] / run.trace["window_s"] * 100.0

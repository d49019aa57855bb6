"""Scheduler: 95th percentile, in ms, of the queue wait (the scheduler's
``admitted_at`` minus the request's arrival) of the requests admitted in
the traced slice."""
from bench.harness import percentile


def read(run):
    waits = run.stats.get("queue_ms")
    return percentile(waits, 95) if waits else None

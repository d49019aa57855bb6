"""Scheduler: 95th percentile, in ms, of the engine's admission turns in
the traced slice: the lengths of the program's ``serve.admit`` host spans
(``ServeEngine.run``: the wave's inputs, its prefill and scatter, the first
tokens read back).  Nothing to read where the program opens no such span."""
from bench.harness import percentile


def read(run):
    admits = run.stats.get("admit_ms")
    return percentile(admits, 95) if admits else None

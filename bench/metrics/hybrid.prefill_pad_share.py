"""Scheduler: share of the positions the prefill programs computed in the
window that were padding, in %: the program's counters
``serve.prefill_pad_tokens`` over ``serve.prefill_tokens`` (pad columns of
a wave's sequence bucket and the dummy rows that fill its batch bucket).
Nothing to read where the program keeps no such counter."""


def read(run):
    total = run.stats.get("prefill_tokens")
    if not total:
        return None
    return run.stats["prefill_pad_tokens"] / total * 100.0

"""Scheduler: tokens emitted per engine loop turn over the slots, in %,
in the traced slice.  Turns are told apart by the token stamps: the tokens
of one turn are appended together, a decode step after the last turn's."""


def read(run):
    turns = run.stats.get("decode_steps")
    if not turns:
        return None
    return run.stats["slice_tokens"] / turns / run.stats["slots"] * 100.0

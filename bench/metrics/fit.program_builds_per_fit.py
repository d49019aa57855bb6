"""Runner (host): programs built per fit, counted by the program inside
the traced window: ``jax.builds`` (every XLA compile or persistent-cache
load) over ``runner.launches`` (one per fit), both from the window's
counter deltas in ``stats["program"]``."""


def read(run):
    program = run.stats.get("program")
    if not program or not program.get("runner.launches"):
        return None
    return program.get("jax.builds", 0) / program["runner.launches"]

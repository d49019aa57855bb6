"""Runner: device time of one round of the rounds program, in ms.  The
rounds program is the program that takes the most device time in the
traced window; its time per execution on one chip, over the rounds it
runs per fit."""


def read(run):
    if run.trace is None or not run.trace["module_s"]:
        return None
    name = max(run.trace["module_s"], key=run.trace["module_s"].get)
    per_run = run.trace["module_s"][name] / run.trace["module_n"][name]
    return per_run / run.stats["rounds"] * 1e3

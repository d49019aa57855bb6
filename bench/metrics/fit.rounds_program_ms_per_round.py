"""Runner: device time of the rounds program per round launched, in ms.
The device time of the programs named ``jit_mli_rounds`` in the traced
window, per chip, over the rounds the runner launched in it
(``runner.rounds`` in ``stats["program"]``)."""

PROGRAM = "jit_mli_rounds"


def read(run):
    program = run.stats.get("program")
    if run.trace is None or not program or not program.get("runner.rounds"):
        return None
    device_s = sum(s for name, s in run.trace["module_s"].items()
                   if name.startswith(PROGRAM))
    if not device_s:
        return None
    return device_s / run.chips / program["runner.rounds"] * 1e3

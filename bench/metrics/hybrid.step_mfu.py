"""Whole step: the hybrid model's FLOP/s over the chip's bf16 peak while
the device is busy, in %, in the traced slice: the forward FLOPs of every
prompt admitted and every token decoded in the slice
(``bench.drivers.serve_hybrid.model_flops``: projections, MLPs, the SSD
recurrence's update and readout, attention over the live context, the LM
head), over the device-busy time of the traced window."""


def read(run):
    flops = run.stats.get("model_flops")
    if (not flops or not run.stats.get("state_bytes") or run.trace is None
            or run.trace["busy_s"] <= 0):
        return None
    return (flops / run.trace["busy_s"]
            / (run.chips * run.peaks["bf16_flops_per_s"]) * 100.0)

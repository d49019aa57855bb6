"""Whole step: model FLOP/s over the chip's bf16 peak while the device is
busy, in %, in the traced slice: the FLOPs ``serve.mfu`` counts, over the
device-busy time of the traced window rather than its length.  Below the
knee the slice's FLOPs are set by the offered load, so ``serve.mfu`` reads
that load; this share rises as the steps that serve it take less device
time, whichever programs run them."""


def read(run):
    flops = run.stats.get("model_flops")
    if not flops or run.trace is None or run.trace["busy_s"] <= 0:
        return None
    return (flops / run.trace["busy_s"]
            / (run.chips * run.peaks["bf16_flops_per_s"]) * 100.0)

"""Whole step: model FLOP/s over the chip's bf16 peak, in %, in the traced
slice.  The FLOPs are counted by ``bench.drivers.serve.model_flops`` for
every prompt admitted and every token decoded in the slice."""


def read(run):
    flops = run.stats.get("model_flops")
    if not flops:
        return None
    return (flops / run.stats["slice_s"]
            / (run.chips * run.peaks["bf16_flops_per_s"]) * 100.0)

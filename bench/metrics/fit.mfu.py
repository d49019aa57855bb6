"""Whole step: model FLOP/s of the fit loop over the chips' bf16 peak, in
%.  4 x d FLOPs per row and round (the margin x . w, and x (s - y) summed
into the gradient), times the rows per second of the traced run's window."""


def read(run):
    rate = run.e2e.get("fit_rows_per_s")
    if not rate:
        return None
    flops = 4.0 * run.stats["features"] * rate
    return flops / (run.chips * run.peaks["bf16_flops_per_s"]) * 100.0

"""Runner: the rounds program's share of its HBM roofline, in %.  The
least bytes one round can move on one chip are its rows of the table read
once plus the weights; at the peak HBM rate that takes bytes / peak, over
the measured device time of a round (``fit.round_device_ms``)."""
import pathlib

from bench.harness import load_module

_round = load_module(pathlib.Path(__file__).with_name("fit.round_device_ms.py"),
                     "bench_metric_fit_round_device_ms")


def read(run):
    round_ms = _round.read(run)
    if not round_ms:
        return None
    d = run.stats["features"]
    rows = run.stats["rows"] // run.chips
    least = (rows * (d + 1) + d) * 4
    return least / run.peaks["hbm_bytes_per_s"] / (round_ms * 1e-3) * 100.0

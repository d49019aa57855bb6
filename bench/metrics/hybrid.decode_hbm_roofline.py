"""Model step: the hybrid decode program's share of its HBM roofline, in %,
in the traced slice.  The least bytes of one decode step are the weights,
every slot's recurrent state (float32 ``h`` and the conv taps) read and
written, and the K/V of the attention layers at the positions live in it;
at the peak HBM rate that takes bytes / peak, over the device time of the
decode program (``jit_serve_decode``).  A trace without it, or a run
without the driver's state count, reads nothing."""

PROGRAM = "jit_serve_decode"


def read(run):
    t = run.trace
    if t is None or not run.stats.get("state_bytes"):
        return None
    names = [n for n in t["module_n"] if n.startswith(PROGRAM)]
    runs = sum(t["module_n"][n] for n in names)
    device_s = sum(t["module_s"][n] for n in names)
    if not runs or not device_s:
        return None
    least = (runs * (run.stats["weight_bytes"] + 2 * run.stats["state_bytes"])
             + run.stats["live_positions"] * run.stats["kv_bytes_per_position"])
    return least / run.peaks["hbm_bytes_per_s"] / device_s * 100.0

"""Model step: the decode program's share of its HBM roofline, in %, in
the traced slice.  The least bytes of one decode step are the weights plus
the K/V of the positions live in it; at the peak HBM rate that takes
bytes / peak, over the decode program's device time.  The decode program
is the engine's ``serve_decode`` jit, ``jit_serve_decode`` in the trace;
a trace without it reads nothing."""

PROGRAM = "jit_serve_decode"


def read(run):
    t = run.trace
    if t is None or not run.stats.get("decode_steps"):
        return None
    names = [n for n in t["module_n"] if n.startswith(PROGRAM)]
    runs = sum(t["module_n"][n] for n in names)
    device_s = sum(t["module_s"][n] for n in names)
    if not runs or not device_s:
        return None
    least = (runs * run.stats["weight_bytes"]
             + run.stats["live_positions"] * run.stats["kv_bytes_per_position"])
    return least / run.peaks["hbm_bytes_per_s"] / device_s * 100.0

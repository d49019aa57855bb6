"""Model step: the decode program's share of its HBM roofline, in %, in
the traced slice.  The least bytes of one decode step are the weights plus
the K/V of the positions live in it; at the peak HBM rate that takes
bytes / peak, over the decode program's device time.  The decode program
is the one the trace runs once per engine loop turn with the most device
time."""


def read(run):
    t = run.trace
    turns = run.stats.get("decode_steps")
    if t is None or not turns:
        return None
    per_turn = [n for n, c in t["module_n"].items() if c >= 0.9 * turns]
    if not per_turn:
        return None
    name = max(per_turn, key=t["module_s"].get)
    least = (t["module_n"][name] * run.stats["weight_bytes"]
             + run.stats["live_positions"] * run.stats["kv_bytes_per_position"])
    return least / run.peaks["hbm_bytes_per_s"] / t["module_s"][name] * 100.0

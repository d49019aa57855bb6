#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with as many TPU chips as the
cell asks for.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last the ``checks``: each number compared
for correctness with its limit, which are also the last lines of standard
error.  Exits nonzero, printing no result, when JAX finds no TPU, fewer chips
than the cell needs, or a ``device_kind`` missing from ``bench/peaks.json``.

JAX's persistent compilation cache is kept in ``.jax_cache/`` at the root of
the checkout, whatever the environment says, so that only the first run of
a cell in a checkout compiles.
"""
import os
import pathlib
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    from bench import harness

    sys.exit(harness.main(t_start=T_START))

"""The reduction from a profiler trace to the benchmark's device numbers
(bench/trace_reduce.py): on plain intervals, and on a small trace recorded
on one TPU v5e (``data/v5e_tiny.xplane.pb``: two jitted programs run three
times inside ``bench.fit`` spans, inside one ``bench.window`` span)."""
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import trace_reduce as tr  # noqa: E402

RECORDED = HERE / "data" / "v5e_tiny.xplane.pb"


def test_union_merges_overlaps_and_busy_clips_to_the_window():
    ivs = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (30, 35, "d")]
    assert tr.union(ivs) == [(0, 15), (20, 35)]
    assert tr.busy_ns(ivs, 10, 32) == 5 + 12


def test_gaps_are_the_complement_inside_the_window():
    ivs = [(10, 20, "a"), (40, 50, "b")]
    assert tr.gaps(ivs, 0, 60) == [(0, 10), (20, 40), (50, 60)]
    assert tr.gaps([], 0, 5) == [(0, 5)]


def test_self_time_leaves_out_nested_ops():
    ivs = [(0, 100, "while"), (10, 30, "fusion"), (40, 60, "fusion"),
           (120, 130, "copy")]
    assert tr.self_time_by_name(ivs) == {"while": 60, "fusion": 40,
                                         "copy": 10}


def test_gaps_are_named_by_the_innermost_open_span():
    spans = [(0, 100, "bench.window"), (10, 40, "bench.fit")]
    assert tr.span_at(spans, 20) == "bench.fit"
    assert tr.span_at(spans, 60) == "bench.window"
    assert tr.span_at(spans, 200) == "no span"


def test_summarize_on_plain_intervals():
    trace = tr.Trace(
        ops={0: [(10, 20, "fusion.1"), (30, 60, "all-gather.2")],
             1: [(10, 50, "fusion.1")]},
        modules={0: [(10, 60, "jit_run")], 1: [(10, 50, "jit_run")]},
        spans=[(0, 100, "bench.window"), (5, 65, "bench.fit")])
    s = tr.summarize(trace, [0, 1])
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx((40 + 40) / 2 * 1e-9)
    assert s["collective_s"] == pytest.approx(30e-9)
    assert s["module_n"] == {"jit_run": 2}
    assert s["device_ops"][0] == ["fusion.1", pytest.approx(50e-9)]
    assert s["idle_gaps"][0] == ["bench.window", pytest.approx(40e-9)]


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tr.window(tr.Trace(ops={}, modules={}, spans=[]))


def test_recorded_v5e_trace():
    trace = tr.load(str(RECORDED))
    assert list(trace.ops) == [0] and len(trace.ops[0]) == 12
    assert len(trace.modules[0]) == 6
    lo, hi = tr.window(trace)
    fits = [s for s in trace.spans if s[2] == "bench.fit"]
    assert len(fits) == 3 and all(lo <= s <= e <= hi for s, e, _ in fits)
    s = tr.summarize(trace, [0])
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["collective_s"] == 0.0
    # the device plane's clock runs 1-2 ms behind the host plane's in this
    # recording: each fit's two programs lie within 2.5 ms before its span
    for start, end, _ in trace.modules[0]:
        assert any(fs - 2.5e6 <= start and end <= fe for fs, fe, _ in fits)


def _serving_view(module_s, module_n):
    import json
    import types

    peaks = json.loads((HERE.parents[1] / "bench" / "peaks.json").read_text())
    return types.SimpleNamespace(
        trace={"module_s": module_s, "module_n": module_n},
        stats={"decode_steps": 50, "weight_bytes": 3e9,
               "live_positions": 1_000_000, "kv_bytes_per_position": 28672},
        chips=1, peaks=peaks["TPU v5 lite"])


def test_decode_roofline_reads_the_decode_program_by_name():
    """A prefill program run as often as the decode step, and with more
    device time, is not the one read."""
    from bench.harness import load_metric

    reader = load_metric("serve.decode_hbm_roofline")
    view = _serving_view(
        {"jit_serve_prefill_ragged(7)": 9.0, "jit_serve_decode(3)": 2.0,
         "jit_serve_scatter(4)": 0.1},
        {"jit_serve_prefill_ragged(7)": 50, "jit_serve_decode(3)": 50,
         "jit_serve_scatter(4)": 50})
    least = 50 * 3e9 + 1_000_000 * 28672
    assert reader.read(view) == pytest.approx(least / 819e9 / 2.0 * 100.0)
    no_decode = _serving_view({"jit_serve_prefill_ragged(7)": 9.0},
                              {"jit_serve_prefill_ragged(7)": 50})
    assert reader.read(no_decode) is None

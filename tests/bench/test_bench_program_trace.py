"""The program's spans in a profiler trace (bench/program_trace.py) and the
readers of the runner's host and device time per fit: on plain intervals,
and on the trace recorded on one TPU v5e (``data/v5e_tiny.xplane.pb``)."""
import pathlib
import sys
import types

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from bench import program_trace as pt  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402
from bench.harness import load_metric  # noqa: E402

RECORDED = HERE / "data" / "v5e_tiny.xplane.pb"
READERS = ("fit.launch_ms_per_fit", "fit.program_builds_per_fit",
           "fit.rounds_program_ms_per_round")


def test_recorded_v5e_trace_clock_skew():
    program = pt.load(str(RECORDED))
    assert program.spans == []               # recorded before the mli. spans
    skew = pt.clock_skew_ns(program, 0)
    assert 1.2e6 <= skew <= 1.3e6
    assert len(program.lags[0]) == 6
    # shifted onto the device's clock, no enqueue begins after its program
    assert all(lag - skew <= 0 for lag in program.lags[0])
    assert min(program.lags[0]) < skew      # the bound is the tightest lag
    assert pt.clock_skew_ns(program, 1) is None


def test_recorded_v5e_trace_gaps_by_span():
    trace = tr.load(str(RECORDED))
    s = pt.summarize(trace, pt.load(str(RECORDED)), [0])
    assert s["clock_skew_ns"] == pt.clock_skew_ns(pt.load(str(RECORDED)), 0)
    idle = s["idle_s_by_span"]
    lo, hi = tr.window(trace)
    busy = tr.busy_ns(trace.ops[0], lo, hi) * 1e-9
    assert sum(idle.values()) == pytest.approx((hi - lo) * 1e-9 - busy)
    # the window's span, shifted too, leaves its last 1.2 ms to no span
    assert set(idle) <= {"bench.window", "bench.fit", "no span"}
    assert {name for name, _ in s["idle_gaps"]} <= set(idle)


def test_merged_summary_leaves_the_existing_readers_as_they_were():
    """Merging ``program_trace.summarize`` into the trace reduction's
    summary, as the wiring would, moves no accepted reader's value."""
    import json

    trace = tr.load(str(RECORDED))
    plain = tr.summarize(trace, [0])
    merged = dict(plain)
    merged.update(pt.summarize(trace, pt.load(str(RECORDED)), [0]))
    peaks = json.loads((HERE.parents[1] / "bench" / "peaks.json").read_text())
    for name in ("fit.api_ms_per_fit", "fit.round_device_ms",
                 "fit.round_hbm_roofline", "fit.mfu", "fit.idle_share",
                 "fit.collective_share"):
        reader = load_metric(name)
        views = [types.SimpleNamespace(
            trace=t, stats={"rows": 1024, "rounds": 1, "features": 1023},
            e2e={"fit_rows_per_s": 1000.0}, chips=1,
            peaks=peaks["TPU v5 lite"]) for t in (plain, merged)]
        assert reader.read(views[1]) == reader.read(views[0]), name


def _trace():
    """A window of two fits on one chip.  Each fit: prepare, a long launch,
    then the rounds program runs on the device; the device clock lags the
    host's by 10, so a program enqueued at host time t + 60 starts at device
    time t + 50."""
    spans = [(0, 200, "bench.window")]
    ops, modules, lags = [], [], []
    for t in (0, 100):
        spans += [(t + 10, t + 95, "bench.fit"), (t + 12, t + 94, "mli.fit"),
                  (t + 14, t + 20, "mli.runner.prepare"),
                  (t + 20, t + 60, "mli.runner.launch")]
        ops.append((t + 50, t + 90, "fusion.1"))
        modules.append((t + 50, t + 90, "jit_mli_rounds(123)"))
        lags.append(10)
    trace = tr.Trace(ops={0: ops}, modules={0: modules},
                     spans=[s for s in spans if s[2].startswith("bench.")])
    program = pt.ProgramTrace(
        spans=[s for s in spans if s[2].startswith("mli.")], lags={0: lags})
    return trace, program


def test_gaps_are_named_by_the_innermost_program_span():
    trace, program = _trace()
    s = pt.summarize(trace, program, [0])
    assert s["clock_skew_ns"] == 10
    by_span = {name: sec * 1e9 for name, sec in s["idle_s_by_span"].items()}
    # device idle 0-50, 90-150, 190-200; shifted, each fit's spans are
    # bench.fit t+0..85 > mli.fit t+2..84 > prepare t+4..10, launch t+10..50
    # and the window -10..190
    assert by_span == pytest.approx({
        "mli.runner.launch": 40 + 40, "mli.runner.prepare": 6 + 6,
        "mli.fit": 2 + 2, "bench.fit": 2 + 2, "bench.window": 10,
        "no span": 10})
    assert [name for name, _ in s["idle_gaps"]] == [
        "mli.runner.launch", "mli.runner.launch", "no span"]
    assert s["idle_gaps"][0][1] == pytest.approx(60e-9)


def test_idle_by_span_leaves_uncovered_time_to_no_span():
    spans = [(10, 20, "mli.fit"), (12, 14, "mli.runner.launch")]
    assert pt.idle_by_span([(0, 5), (11, 13), (18, 30)], spans) == {
        "no span": 5 + 10, "mli.fit": 1 + 2, "mli.runner.launch": 1}


def _view(trace=None, program=None, chips=1):
    return types.SimpleNamespace(
        trace=trace, stats={"rows": 64, "rounds": 10, "features": 8,
                            **({"program": program} if program else {})},
        e2e={}, chips=chips, peaks={})


def _merged():
    trace, program = _trace()
    s = tr.summarize(trace, [0])
    s.update(pt.summarize(trace, program, [0]))
    return s


def test_readers_on_plain_intervals():
    counters = {"runner.launches": 2, "runner.rounds": 20, "jax.builds": 2,
                "jax.cache_loads": 2}
    view = _view(_merged(), counters)
    # each launch: device time t+10..50, no device op in it
    assert load_metric("fit.launch_ms_per_fit").read(view) == \
        pytest.approx(40e-6)
    assert load_metric("fit.program_builds_per_fit").read(view) == 1.0
    # 2 x 40 ns of jit_mli_rounds over 20 rounds
    assert load_metric("fit.rounds_program_ms_per_round").read(view) == \
        pytest.approx(80e-9 / 20 * 1e3)


@pytest.mark.parametrize("name", READERS)
def test_readers_read_nothing_without_the_programs_spans_or_counters(name):
    trace, _ = _trace()
    reader = load_metric(name)
    assert reader.read(_view()) is None
    # a parent's trace: bench. spans only, no skew, no counters
    assert reader.read(_view(tr.summarize(trace, [0]))) is None
    # counters without rounds or launches
    assert reader.read(_view(tr.summarize(trace, [0]), {"jax.builds": 3})) \
        is None

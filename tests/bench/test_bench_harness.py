"""The benchmark harness: discovery by name, device refusal, and the
arithmetic of its end-to-end metrics (bench/harness.py,
bench/drivers/serve.py)."""
import json
import math
import pathlib
import shutil
import sys
import time
import types

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import harness  # noqa: E402
from bench.drivers import serve  # noqa: E402

DUMMY_DRIVER = '''
def run(ctx):
    start = ctx.begin_window()
    ctx.start_trace()
    ctx.stop_trace()
    ctx.read_memory_peak()
    return {"e2e": {"widgets_per_s": 3.0}, "stats": {"widgets": 7},
            "checks": {"widget_err": (0.5, ctx.config["limit"])},
            "attempted": 7, "failed": 0, "compiles_in_window": {}}
'''
DUMMY_METRIC = '''
def read(run):
    return run.stats["widgets"] * 2.0
'''


def _dummy_root(tmp_path, limit=1.0):
    """A benchmark tree holding one cell, config, mix, driver and per-layer
    metric that the real benchmark does not have."""
    bench = tmp_path / "bench"
    for sub in ("workloads", "configs", "traffic", "drivers", "metrics"):
        (bench / sub).mkdir(parents=True)
    spec = {
        "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
        "workloads": [{"name": "toy.mix", "config": "toy", "traffic": "mix",
                       "chips": 1}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower"},
            {"name": "widgets_per_s", "unit": "widgets/s",
             "better": "higher", "workloads": ["toy.mix"]}],
        "per_layer": [
            {"name": "toy.double", "unit": "widgets", "better": "higher",
             "moves": "widgets_per_s", "workloads": ["toy.mix"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "workloads" / "toy.mix.json").write_text(json.dumps(
        {"config": "toy", "traffic": "mix", "chips": 1, "driver": "toy"}))
    (bench / "configs" / "toy.json").write_text(json.dumps({"limit": limit}))
    (bench / "traffic" / "mix.json").write_text(json.dumps({"rate": 1}))
    (bench / "drivers" / "toy.py").write_text(DUMMY_DRIVER)
    (bench / "metrics" / "toy.double.py").write_text(DUMMY_METRIC)
    shutil.copy(REPO / "bench" / "peaks.json", bench / "peaks.json")
    return tmp_path


def test_new_cell_and_metric_files_are_found_by_name(tmp_path):
    root = _dummy_root(tmp_path)
    cell = harness.find_cell("toy.mix", root)
    assert (cell.driver, cell.config, cell.traffic) == (
        "toy", {"limit": 1.0}, {"rate": 1})
    assert [m["name"] for m in cell.end_to_end] == ["setup_s",
                                                    "widgets_per_s"]
    assert [m["name"] for m in cell.per_layer] == ["toy.double"]
    out = harness.run_cell(cell, seed=2**31 + 1, seconds=1.0, trace=False,
                           t_start=time.perf_counter(), require_tpu=False,
                           root=root)
    assert out["correct"] is True
    assert out["metrics"]["widgets_per_s"] == {"value": 3.0,
                                               "unit": "widgets/s"}
    assert out["metrics"]["setup_s"]["value"] >= 0
    assert list(out)[-1] == "checks"
    assert out["checks"] == {"widget_err": {"value": 0.5, "limit": 1.0}}
    traced = harness.run_cell(cell, seed=1, seconds=1.0, trace=True,
                              t_start=time.perf_counter(), require_tpu=False,
                              root=root)
    assert traced["metrics"] == {"toy.double": {"value": 14.0,
                                                "unit": "widgets"}}


def test_a_check_over_its_limit_is_not_correct(tmp_path):
    root = _dummy_root(tmp_path, limit=0.25)
    out = harness.run_cell(harness.find_cell("toy.mix", root), seed=1,
                           seconds=1.0, trace=False,
                           t_start=time.perf_counter(), require_tpu=False,
                           root=root)
    assert out["correct"] is False


def test_cell_file_disagreeing_with_benchmark_json_is_refused(tmp_path):
    root = _dummy_root(tmp_path)
    (root / "bench" / "workloads" / "toy.mix.json").write_text(json.dumps(
        {"config": "toy", "traffic": "mix", "chips": 4, "driver": "toy"}))
    with pytest.raises(harness.Refused):
        harness.find_cell("toy.mix", root)
    with pytest.raises(harness.Refused):
        harness.find_cell("no.such.cell", root)


def test_the_repository_cells_resolve():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        cell = harness.find_cell(w["name"])
        assert (REPO / "bench" / "drivers" / f"{cell.driver}.py").is_file()
        for m in cell.per_layer:
            assert hasattr(harness.load_metric(m["name"]), "read")
        assert cell.end_to_end[0]["name"] == "setup_s"


def _dev(platform="tpu", kind="TPU v5 lite"):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_devices_are_refused_without_a_known_tpu():
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    assert harness.require_devices(1, peaks, [_dev()]) == [_dev()]
    with pytest.raises(harness.Refused, match="needs a TPU"):
        harness.require_devices(1, peaks, [_dev("cpu", "cpu")])
    with pytest.raises(harness.Refused, match="not in bench/peaks.json"):
        harness.require_devices(1, peaks, [_dev(kind="TPU v9 imaginary")])
    with pytest.raises(harness.Refused, match="needs 4 chips"):
        harness.require_devices(4, peaks, [_dev()])


def test_main_without_a_tpu_exits_nonzero_and_prints_no_result(capsys):
    rc = harness.main(["--workload", "logreg-160k.resident", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_percentile_is_nearest_rank_and_counts_failures_as_misses():
    assert harness.percentile(list(range(1, 101)), 95) == 95
    assert harness.percentile([1.0] * 96 + [math.inf] * 4, 95) == 1.0
    assert harness.percentile([1.0] * 94 + [math.inf] * 6, 95) == math.inf
    assert math.isnan(harness.percentile([], 95))


def test_stamped_tokens_record_the_clock_and_call_the_hook():
    seen = []
    toks = serve.StampedTokens(seen.append)
    t0 = time.perf_counter()
    toks.append(5)
    toks.append(9)
    assert list(toks) == [5, 9] and len(toks.times) == 2
    assert t0 <= toks.times[0] <= toks.times[1]
    assert seen == toks.times


def _req(arrival, prompt_len, times):
    toks = serve.StampedTokens(lambda now: None)
    toks.extend(range(len(times)))
    toks.times = list(times)
    return types.SimpleNamespace(arrival=arrival, out_tokens=toks,
                                 prompt=np.zeros(prompt_len, np.int32))


def test_window_metrics_take_the_whole_window_and_every_request():
    start, seconds = 100.0, 10.0
    reqs = [_req(0.0, 4, [100.5, 101.0, 101.5]),
            # ends after the window: its last token and gap are not counted
            _req(1.0, 4, [102.0, 109.0, 111.0]),
            # never served: a miss for the first-token tail
            _req(2.0, 4, [])]
    m = serve.window_metrics(reqs, start, seconds)
    assert m["serve_tokens_per_s"] == 5 / 10.0
    assert m["ttft_p90_ms"] == math.inf
    assert m["itl_p95_ms"] == pytest.approx(7000.0)
    m2 = serve.window_metrics(reqs[:2], start, seconds)
    assert m2["ttft_p90_ms"] == pytest.approx(1000.0)


def test_engine_loop_turns_are_told_apart_by_the_stamps():
    t = np.array([0.0, 1e-5, 2e-5, 0.013, 0.01301, 0.026])
    assert list(serve.steps(t)) == [0, 0, 0, 1, 1, 2]


def test_benchmark_json_keeps_to_its_format():
    import re

    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert 1 <= spec["run_seconds"] <= 51
    for p in spec["paths"]:
        assert (REPO / p).is_dir()
    configs = {c["name"] for c in spec["configs"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and (REPO / c["file"]).is_file()
        assert all(name.match(k) for k in c["reduced"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    cells = [w["name"] for w in spec["workloads"]]
    pairs = {(w["config"], w["traffic"]) for w in spec["workloads"]}
    assert len(set(cells)) == len(cells) == len(pairs)
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(cells) // 2)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", cells)
    # a full check of 24 cells at this window fits the driver's time
    runs = 2 + 14 * 24
    assert runs * (spec["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200

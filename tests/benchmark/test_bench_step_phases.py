"""The two readers of the engine's own step tracing, on a hand-made
fixture under benchmark/testdata/step_phases/: ``step_rows`` (the
serve_step rows of spans.jsonl inside loadgen.json's window) and
``phase_idle`` (device-idle gaps by ``serve:`` phase). Each gives the
number worked out by hand, keeps to the window, and reads None where
there is nothing to read."""

import json
import shutil

import pytest

from benchmark import layers, spec

FIXTURE = spec.ROOT / "benchmark/testdata/step_phases"
NEW_METRICS = {
    "baichuan7b.chat-online": {
        "step_host_p50_ms.online", "admit_host_p50_ms",
        "prefill_step_share_pct", "idle_admit_pct.online",
        "idle_step_loop_pct.online"},
    "baichuan7b.batch-offline": {
        "step_host_p50_ms.batch", "idle_admit_pct.batch",
        "idle_step_loop_pct.batch"}}


def _reader(name):
    return spec.load_module(spec.ROOT, spec.load_benchmark(),
                            f"layer_metrics/readers/{name}.py")


def _params(metric):
    return spec.layer_metric_file(metric)["params"]


@pytest.fixture(scope="module")
def trace():
    with open(FIXTURE / "trace.json", encoding="utf-8") as fh:
        return json.load(fh)


def test_step_rows_keep_to_the_window():
    rows, window_s = _reader("step_rows").window_rows(FIXTURE)
    assert window_s == 10.0
    # 99.5 lies before the window, 110.0 is its end: both are out;
    # the serve_prefill span in the file is no step row
    assert [row["mono_start"] for row in rows] == \
        [100.5, 101.0, 102.0, 103.0, 104.0]
    assert [round(row["wall_ms"], 6) for row in rows] == \
        [50.0, 52.0, 160.0, 56.0, 110.0]


@pytest.mark.parametrize("metric,expected", [
    # steps without a prefill, wall less readback: 12, 14, 16
    ("step_host_p50_ms.online", 14.0),
    ("step_host_p50_ms.batch", 14.0),
    # steps with one: (1.0 + 0.6) / 2 and (0.8 + 0.4) / 1
    ("admit_host_p50_ms", 1.0),
    # 100 + 50 ms of prefill in a 10 s window
    ("prefill_step_share_pct", 1.5),
])
def test_step_row_metrics_by_hand(metric, expected):
    reader = _reader("step_rows")
    rows, window_s = reader.window_rows(FIXTURE)
    assert reader.value(rows, window_s, _params(metric)) == \
        pytest.approx(expected)


def test_step_rows_describe_themselves_for_perf_md():
    reader = _reader("step_rows")
    rows, _ = reader.window_rows(FIXTURE)
    line = reader.describe(rows, ("admit", "prefill", "slot_update",
                                  "grow_pages", "dispatch",
                                  "readback", "emit"))
    # mean slots (2+2+2+4+4)/5; phases 49+51+158.6+55+109.2 of 428 ms
    assert "5 (3 without a prefill)" in line
    assert "wall p50 56.000 ms" in line
    assert "mean slots_active 2.800 of 4" in line
    assert "phases cover 98.79 %" in line


def test_idle_gaps_go_to_the_phase_that_covers_their_middle(trace):
    reader = _reader("phase_idle")
    gaps = reader.split(trace)
    # gaps [3000,4000) admit, [5000,7000) slot_update, [9000,12000)
    # readback, [13000,15000) nothing; bench:engine.step covers them
    # all and is not a serve: span
    assert gaps == {"admit": pytest.approx(1000e-9),
                    "slot_update": pytest.approx(2000e-9),
                    "readback": pytest.approx(3000e-9),
                    "uncovered": pytest.approx(2000e-9)}
    slice_s = 20000e-9
    for cell in ("online", "batch"):
        assert reader.value(
            gaps, slice_s, _params(f"idle_admit_pct.{cell}")) == \
            pytest.approx(15.0)
        assert reader.value(
            gaps, slice_s, _params(f"idle_step_loop_pct.{cell}")) == \
            pytest.approx(15.0)


def test_both_readers_read_none_where_there_is_nothing(trace, tmp_path):
    rows_reader, idle_reader = _reader("step_rows"), _reader("phase_idle")
    assert rows_reader.window_rows(tmp_path) == ([], 0.0)
    assert rows_reader.value([], 10.0, _params("admit_host_p50_ms")) \
        is None
    # a program without the rows (the parent commit): other spans only
    shutil.copy(FIXTURE / "loadgen.json", tmp_path)
    (tmp_path / "spans.jsonl").write_text(json.dumps(
        {"kind": "serve_prefill", "start": 1.0, "end": 2.0,
         "attrs": {"request_id": "bench-1"}}) + "\n")
    rows, window_s = rows_reader.window_rows(tmp_path)
    assert rows == [] and rows_reader.value(
        rows, window_s, _params("prefill_step_share_pct")) is None
    # steps, but none with a prefill
    rows, window_s = rows_reader.window_rows(FIXTURE)
    decode_only = [row for row in rows if not row["prefills"]]
    assert rows_reader.value(decode_only, window_s,
                             _params("admit_host_p50_ms")) is None
    # no serve: annotation (the parent commit), no device plane (CPU)
    host_only = {"planes": [p for p in trace["planes"]
                            if not p["name"].startswith("/device")]}
    bench_only = {"planes": [
        trace["planes"][0],
        {"name": "/host:CPU", "lines": [
            {"name": "t", "events": [["bench:engine.step", 0, 20000]]}
        ]}]}
    assert idle_reader.split(host_only) is None
    assert idle_reader.split(bench_only) is None
    assert idle_reader.value(None, 4.0, {"phases": ["admit"]}) is None


@pytest.mark.parametrize("cell", sorted(NEW_METRICS))
def test_a_traced_run_reads_the_new_metrics_from_the_out_dir(
        cell, trace, tmp_path, capsys):
    """layers.read_all as run.py calls it: the readers fetch the rows
    and the window from the run's own output directory, which the
    driver names in ``obs["out_dir"]``. Without an xplane file there
    the idle metrics are left out; given the split, they are read
    against the slice the driver's profile names."""
    for name in ("spans.jsonl", "loadgen.json"):
        shutil.copy(FIXTURE / name, tmp_path)
    loaded = spec.load_cell(cell)
    obs = {"series": {}, "counters": {}, "peaks": None,
           "out_dir": tmp_path,
           "profile": {"events": {}, "window_s": 20000e-9,
                       "busy_s": 12000e-9}}
    got = layers.read_all(loaded, obs)
    rows_metrics = {n for n in NEW_METRICS[cell] if "idle" not in n}
    assert set(got) == rows_metrics
    assert "serve_step rows in the window: 5" in capsys.readouterr().out
    obs["phase_idle"] = _reader("phase_idle").split(trace)
    got = layers.read_all(loaded, obs)
    assert set(got) == NEW_METRICS[cell]
    idle_admit = next(n for n in got if n.startswith("idle_admit"))
    assert got[idle_admit] == {"value": pytest.approx(15.0),
                               "unit": "%"}
    # an untraced-program run: nothing under the out dir
    assert layers.read_all(loaded, {"series": {}, "counters": {},
                                    "peaks": None, "profile": None,
                                    "out_dir": tmp_path / "empty"}) \
        == {}
    # ... and no directory named: whatever another run left in the
    # checkout's own is not this run's to read
    assert layers.read_all(loaded, {"series": {}, "counters": {},
                                    "peaks": None, "profile": None}) \
        == {}

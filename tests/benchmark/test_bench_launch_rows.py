"""The reader of the launches the engine landed (``launch_rows``: the
``landed`` lists and ``no_work_seconds`` of the serve_step rows), on a
hand-made fixture under tests/benchmark/launch_rows/ (the rows of
benchmark/testdata/step_phases with the lists added): each ``value``
gives the number worked out by hand, keeps to the window, reads None
where a program writes no such list, and a traced rehearsal of each
cell that lists the new metrics reads every one of them."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import layers, spec

FIXTURE = spec.ROOT / "tests/benchmark/launch_rows"
PARENT = spec.ROOT / "benchmark/testdata/step_phases"   # no lists
NEW_METRICS = {
    "baichuan7b.chat-online": {
        "prefill_device_share_pct.online", "prefill_padding_pct.online",
        "decode_launch_p50_ms.online", "host_behind_pct.online",
        "engine_no_work_pct.online"},
    "baichuan7b.batch-offline": {
        "prefill_device_share_pct.batch", "prefill_ms_per_ktoken.batch",
        "prefill_padding_pct.batch", "decode_launch_p50_ms.batch",
        "host_behind_pct.batch"}}


def _reader(name="launch_rows"):
    return spec.load_module(spec.ROOT, spec.load_benchmark(),
                            f"layer_metrics/readers/{name}.py")


def _params(metric):
    return spec.layer_metric_file(metric)["params"]


def _rows(where=FIXTURE):
    return _reader("step_rows").window_rows(where)


def test_the_launches_keep_to_the_window():
    reader = _reader()
    rows, window_s = _rows()
    # the rows of 99.5 and 110.0 are outside, with their launches
    assert [x["period_ms"] for x in reader.launches(rows, "decode")] \
        == [10.0, 12.0, 14.0, 16.0]
    assert [x["request_id"] for x in reader.launches(rows, "prefill")] \
        == ["bench-1", "bench-2", "bench-3"]
    assert len(reader.launches(rows)) == 7 and window_s == 10.0


@pytest.mark.parametrize("metric,expected", [
    # prefills of 60 + 20 + 40 ms in a 10 s window
    ("prefill_device_share_pct.online", 1.2),
    ("prefill_device_share_pct.batch", 1.2),
    # ... over buckets of 512 + 256 + 256 tokens
    ("prefill_ms_per_ktoken.batch", 117.1875),
    # ... of which 312 + 128 + 200 were the prompts' own
    ("prefill_padding_pct.online", 37.5),
    ("prefill_padding_pct.batch", 37.5),
    # decode launches of 10, 12, 14, 16 ms
    ("decode_launch_p50_ms.online", 13.0),
    ("decode_launch_p50_ms.batch", 13.0),
    # one decode and one prefill of the seven found ready
    ("host_behind_pct.online", 100.0 * 2 / 7),
    ("host_behind_pct.batch", 100.0 * 2 / 7),
    # dry spells of 0.5 and 0.25 s ended inside the window
    ("engine_no_work_pct.online", 7.5),
])
def test_launch_metrics_by_hand(metric, expected):
    rows, window_s = _rows()
    assert _reader().value(rows, window_s, _params(metric)) == \
        pytest.approx(expected)


def test_it_reads_none_where_there_is_nothing(tmp_path):
    reader = _reader()
    every = [name for names in NEW_METRICS.values() for name in names]
    # no rows at all; rows of a program that writes no such list
    for rows, window_s in (([], 10.0), _rows(tmp_path), _rows(PARENT)):
        for metric in every:
            assert reader.value(rows, window_s, _params(metric)) is None
    assert len(_rows(PARENT)[0]) == 5
    # launches of one kind only
    rows, window_s = _rows()
    decode_only = [dict(row, landed=[x for x in row["landed"]
                                     if x["kind"] == "decode"])
                   for row in rows]
    assert reader.value(decode_only, window_s,
                        _params("prefill_ms_per_ktoken.batch")) is None
    assert reader.value(decode_only, window_s,
                        _params("decode_launch_p50_ms.batch")) == 13.0
    # and through read(): no directory named, nothing under it
    for obs in ({}, {"out_dir": tmp_path}):
        assert reader.read(obs, _params(every[0])) is None


def test_the_launches_describe_themselves_for_perf_md():
    reader = _reader()
    rows, window_s = _rows()
    line = reader.describe(rows, window_s)
    assert "decode 4 (p50 13.0 ms, 0.052 s)" in line
    assert "prefill 3 (0.120 s" in line
    assert "256 x2 mean 30.00 ms, 512 x1 mean 60.00 ms" in line
    assert "tokens 640 of 1024 padded" in line
    assert "found ready 2 of 7" in line and "no_work 0.750 s" in line
    # against a device trace: the launches inside the slice, by kind,
    # beside the trace's own program launches
    profile = {"started": 101.5, "stopped": 104.06, "trace": {"planes": [
        {"name": "/host:CPU", "lines": []},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [["fusion.1", 0, 5]]},
            {"name": "XLA Modules", "events": [
                ["jit__decode_step(123)", 0, 13_900_000],
                ["jit__prefill_paged(45)", 14_000_000, 59_000_000],
                ["jit__prefill_paged(45)", 74_000_000, 19_000_000],
                ["jit__decode_step(123)", 2_000_000_000, 15_900_000],
            ]}]}]}}
    line = reader.against_the_trace(rows, profile)
    # the 12 ms step landed before the slice, the 40 ms prefill began
    # inside it and landed inside it
    assert "decode 2 launches, 0.0300 s, mean 15.000 ms, ready 0" in line
    assert "prefill 3 launches, 0.1200 s, mean 40.000 ms, ready 1" in line
    assert "jit__prefill_paged 2 launches, 0.0780 s, mean 39.000 ms" \
        in line
    assert "jit__decode_step 2 launches, 0.0298 s, mean 14.900 ms" in line


@pytest.mark.parametrize("cell", sorted(NEW_METRICS))
def test_read_all_reads_them_from_the_out_dir(cell, tmp_path, capsys):
    for name in ("spans.jsonl", "loadgen.json"):
        shutil.copy(FIXTURE / name, tmp_path)
    loaded = spec.load_cell(cell)
    assert NEW_METRICS[cell] <= {m["name"] for m in loaded.per_layer}
    obs = {"series": {}, "counters": {}, "peaks": None,
           "out_dir": tmp_path, "profile": None}
    got = layers.read_all(loaded, obs)
    assert NEW_METRICS[cell] <= set(got)
    assert all(got[name]["unit"] for name in NEW_METRICS[cell])
    out = capsys.readouterr().out
    assert out.count("launches landed in the window: decode 4") == 1
    # the parent's rows: the new metrics are left out, the others stay
    for name in ("spans.jsonl", "loadgen.json"):
        shutil.copy(PARENT / name, tmp_path)
    old = layers.read_all(loaded, {"series": {}, "counters": {},
                                   "peaks": None, "out_dir": tmp_path,
                                   "profile": None})
    assert old and not NEW_METRICS[cell] & set(old)
    assert set(old) == set(got) - NEW_METRICS[cell]


@pytest.mark.parametrize("cell", sorted(NEW_METRICS))
def test_a_traced_rehearsal_reads_every_new_metric(cell, tmp_path):
    """run.py --trace 1 at the tiny size on the CPU, from a copy of
    the checkout's files (two runs in one checkout share .bench_out):
    the engine's own rows feed every new metric of the cell."""
    root = tmp_path / "copy"
    keep = shutil.ignore_patterns(
        ".git", ".bench_out", ".proof", "chiprun_out", "__pycache__",
        ".jax_compile_cache", "*.pyc")
    for name in ("benchmark", "batch_shipyard_tpu", "tests/benchmark"):
        shutil.copytree(spec.ROOT / name, root / name, ignore=keep)
    shutil.copy(spec.ROOT / "BENCHMARK.json", root)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_compile_cache"))
    env.pop("BENCH_RUN", None)
    env.pop("PYTHONPATH", None)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell,
         "--seed", str(2**31 + 36), "--seconds", "3", "--trace", "1",
         "--rehearse-tiny"], cwd=root, env=env, capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [l for l in done.stdout.splitlines() if l.strip()]
    assert json.loads(lines[-1])["correct"] is True
    rehearsed = next(l for l in lines
                     if l.startswith("rehearsal values"))
    values = json.loads(rehearsed[rehearsed.index("{"):])
    assert NEW_METRICS[cell] <= set(values)
    padding = next(v["value"] for name, v in values.items()
                   if name.startswith("prefill_padding_pct."))
    assert 0 <= padding < 100
    assert any(l.startswith("launches landed in the window: decode ")
               for l in lines)
    assert "serve_stall" not in done.stderr

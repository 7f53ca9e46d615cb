"""BENCHMARK.json against the contract's file rules, and the harness
driven by data: a made-up cell, traffic file and per-layer metric added
as NEW files (in a temporary copy) are picked up with no edit to an
existing file."""

import json
import shutil

from benchmark import layers, spec


def test_benchmark_json_holds_to_the_contract():
    assert spec.validate() == []
    bench = spec.load_benchmark()
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    for workload in bench["workloads"]:
        assert len(workload["why"]) <= 200
    for config in bench["configs"]:
        data = json.load(open(spec.ROOT / config["file"]))
        for key in ("source", "reduced", "assumed", "departures",
                    "deployment"):
            assert key in data, (config["name"], key)
        assert data["reduced"] == config["reduced"]
        assert any("tie" in d for d in data["departures"])
        # no width differs from the source
        assert (data["hidden_size"], data["intermediate_size"],
                data["num_attention_heads"], data["vocab_size"]) == \
            (4096, 11008, 32, 64000)


def test_every_per_layer_metric_moves_what_its_cells_report():
    bench = spec.load_benchmark()
    reported = {
        w["name"]: {m["name"] for m in spec.load_cell(w["name"]).end_to_end}
        for w in bench["workloads"]}
    for metric in bench["per_layer"]:
        for cell in metric["workloads"]:
            assert metric["moves"] in reported[cell], metric["name"]
    # first-token time is not reported (PERF.md, section 2): nothing
    # may claim to move it, or stand in for it under another's name
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert not any("ttft" in name for name in names)


def test_every_layer_metric_has_a_file_and_a_reader():
    bench = spec.load_benchmark()
    for metric in bench["per_layer"]:
        definition = spec.layer_metric_file(metric["name"])
        assert definition["moves"] == metric["moves"]
        assert definition["layer"] == metric["layer"]
        assert spec.load_module(
            spec.ROOT, bench,
            f"layer_metrics/readers/{definition['reader']}.py").read


def test_new_cell_traffic_and_metric_are_only_new_files(tmp_path):
    root = tmp_path / "copy"
    shutil.copytree(spec.ROOT / "benchmark", root / "benchmark")
    bench = spec.load_benchmark()
    before = {p: p.read_bytes()
              for p in (root / "benchmark").rglob("*") if p.is_file()}
    # the made-up additions: files ...
    traffic = json.load(open(root / "benchmark/traffic/chat-online.json"))
    traffic["arrivals"]["rate_per_s"] = 2.5
    (root / "benchmark/traffic/made-up.json").write_text(
        json.dumps(traffic))
    (root / "benchmark/layer_metrics/made_up_p10_ms.json").write_text(
        json.dumps({"name": "made_up_p10_ms", "layer": "front end",
                    "unit": "ms", "moves": "tpot_p95_ms",
                    "reader": "series_percentile",
                    "params": {"series": "gen_late_ms", "pct": 10}}))
    # ... and entries
    bench["workloads"].append(
        {"name": "baichuan7b.made-up",
         "config": "baichuan-7b-serve-1chip", "traffic": "made-up",
         "chips": 1, "why": "a made-up cell"})
    for metric in bench["end_to_end"]:
        if metric["name"] == "tpot_p95_ms":
            metric["workloads"].append("baichuan7b.made-up")
    bench["per_layer"].append(
        {"name": "made_up_p10_ms", "unit": "ms", "better": "lower",
         "source": "host_clock", "layer": "front end",
         "moves": "tpot_p95_ms", "workloads": ["baichuan7b.made-up"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert spec.validate(root) == []
    cell = spec.load_cell("baichuan7b.made-up", root)
    assert cell.kind == "serve-open"
    assert cell.traffic["arrivals"]["rate_per_s"] == 2.5
    assert [m["name"] for m in cell.per_layer] == ["made_up_p10_ms"]
    obs = {"series": {"gen_late_ms": [1.0, 2.0, 3.0]}, "counters": {}}
    read = layers.read_all(cell, obs, root, bench)
    assert read == {"made_up_p10_ms": {"value": 1.2, "unit": "ms"}}
    # no file that was there has changed
    assert all(p.read_bytes() == data for p, data in before.items())


def test_a_reader_that_finds_nothing_leaves_the_metric_out():
    cell = spec.load_cell("baichuan7b.batch-offline")
    obs = {"series": {}, "counters": {}, "profile": None, "peaks": None}
    assert layers.read_all(cell, obs) == {}

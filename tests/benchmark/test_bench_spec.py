"""BENCHMARK.json against the contract's file rules, and the harness
driven by data: a made-up cell, traffic file and per-layer metric added
as NEW files (in a temporary copy) are picked up with no edit to an
existing file, and so is a second MODEL (a module, its reference, a
configuration file and entries), rehearsed to ``correct: true``, and a
ROUTED one (a module that declares decision_layers, judged on the
timed path's own choices). No file of the benchmark outside models/,
reference/ and configs/ names an architecture, and none reads a
private name off the engine."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import layers, spec

WIDTH_KEY = re.compile(
    r"(hidden|intermediate|latent|state|proj\w*|head)_size|_dim$|"
    r"_rank$|^num_(attention|key_value)_heads$|expand|experts_per_tok")


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
        # no width differs from the source: none may be cut at all
        assert not [k for k in data["reduced"] if WIDTH_KEY.search(k)]
        assert set(data.get("published", {})) == set(data["reduced"])
        if config["name"] == "baichuan-7b-serve-1chip":
            assert any("tie" in d for d in data["departures"])
            assert (data["hidden_size"], data["intermediate_size"],
                    data["num_attention_heads"], data["vocab_size"]) \
                == (4096, 11008, 32, 64000)


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


def _copy_of_the_benchmark(tmp_path):
    """(the copy's root, {file that is there: its bytes})."""
    root = tmp_path / "copy"
    shutil.copytree(spec.ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", root)
    return root, {p: p.read_bytes()
                  for p in (root / "benchmark").rglob("*") if p.is_file()}


def test_new_cell_traffic_and_metric_are_only_new_files(tmp_path):
    root, before = _copy_of_the_benchmark(tmp_path)
    bench = spec.load_benchmark()
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


@pytest.mark.parametrize(
    "cell", [w["name"] for w in spec.load_benchmark()["workloads"]])
def test_a_reader_that_finds_nothing_leaves_the_metric_out(cell):
    obs = {"series": {}, "counters": {}, "profile": None, "peaks": None}
    assert layers.read_all(spec.load_cell(cell), obs) == {}


def _python(root, *args, **env):
    """A Python of its own started in ``root``, with the program (and
    nothing else of this checkout) importable after the copy's own
    files."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(spec.ROOT), **env)
    env.pop("BENCH_RUN", None)
    return subprocess.run([sys.executable, *args], cwd=root, env=env,
                          capture_output=True, text=True, timeout=900)


def _validate(root) -> list:
    done = _python(root, "-c", "import json; from benchmark import spec;"
                   " print(json.dumps(spec.validate()))")
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.splitlines()[-1])


def test_a_second_model_is_only_new_files_and_entries(tmp_path):
    """A second model arrives in a copy of the tree as NEW files (a
    module under models/, a reference under reference/, a configuration
    file, a per-layer metric's file) and entries of BENCHMARK.json; its
    cell is rehearsed to ``correct: true`` and no file that was there
    has changed. The stand-in is the dense block under another module
    name, with another initialisation and its own copy of the
    reference: the program serves no second architecture steadily yet.
    A tree of another SHAPE is shown in test_bench_flops_and_kernels."""
    root, before = _copy_of_the_benchmark(tmp_path)
    bench = spec.load_benchmark()
    base = bench["configs"][0]
    # the files: a reference of its own ...
    shutil.copy(root / "benchmark/reference/plain.py",
                root / "benchmark/reference/standin_plain.py")
    # ... a model module that calls it and draws its kernels narrower
    source = (root / "benchmark/models/dense_mha.py").read_text()
    for old, new in (
            ("from benchmark.reference import plain",
             "from benchmark.reference import standin_plain as plain"),
            ('("normal", fan_in)', '("normal", 2.25 * fan_in)')):
        assert source.count(old) == 1
        source = source.replace(old, new)
    (root / "benchmark/models/standin.py").write_text(source)
    # ... a configuration file that names it ...
    config = json.load(open(root / base["file"]))
    assert config["model_module"] == "dense_mha"
    config["model_module"] = "standin"
    (root / "benchmark/configs/standin-serve-1chip.json").write_text(
        json.dumps(config))
    # ... and a per-layer metric of its cell
    metric = json.load(open(
        root / "benchmark/layer_metrics/decode_step_p50_ms.batch.json"))
    metric["name"] = "decode_step_p50_ms.standin"
    (root / "benchmark/layer_metrics/decode_step_p50_ms.standin.json"
     ).write_text(json.dumps(metric))
    # the entries
    bench["configs"].append(dict(
        base, name="standin-serve-1chip",
        file="benchmark/configs/standin-serve-1chip.json"))
    bench["workloads"].append(
        {"name": "standin.batch-offline",
         "config": "standin-serve-1chip", "traffic": "batch-offline",
         "chips": 1, "why": "a second model on traffic that is there"})
    for entry in bench["end_to_end"]:
        if entry["name"] == "serve_tokens_per_s":
            entry["workloads"].append("standin.batch-offline")
    bench["per_layer"].append(
        {"name": "decode_step_p50_ms.standin", "unit": "ms",
         "better": "lower", "source": "host_clock",
         "layer": metric["layer"], "moves": "serve_tokens_per_s",
         "workloads": ["standin.batch-offline"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    assert _validate(root) == []
    done = _python(
        root, "benchmark/run.py", "--workload", "standin.batch-offline",
        "--seed", str(2**31 + 26), "--seconds", "3", "--trace", "1",
        "--rehearse-tiny",
        # a compile cache of its own: an entry written into a shared
        # one would count as compiled inside another run's window
        JAX_COMPILATION_CACHE_DIR=str(root / ".jax_compile_cache"))
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [l for l in done.stdout.splitlines() if l.strip()]
    result = json.loads(lines[-1])
    assert result["correct"] is True, done.stdout[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert any(l.startswith("check ") and "(limit" in l for l in lines)
    # its per-layer metric was read, by the reader that was there
    rehearsed = next(l for l in lines if l.startswith("rehearsal values"))
    assert '"decode_step_p50_ms.standin"' in rehearsed
    assert '"decode_step_p50_ms.batch"' not in rehearsed
    # no file that was there has changed
    assert all(p.read_bytes() == data for p, data in before.items())


def test_a_routed_model_is_only_new_files_and_entries(tmp_path):
    """The routed twin: a model whose layers choose k of n experts
    arrives in a copy of the tree as NEW files and entries: a module
    with ``decision_layers``, its reference, a configuration file with
    a ``check.slack_from``, a limit on routing_rejected_share and a
    ``check.control``. Its cell is rehearsed to ``correct: true`` with
    the reference run on the timed path's own choices and
    routing_rejected_share printed beside its limit, and no file that
    was there has changed. The model is tests/benchmark's stand-in.
    Because the PROGRAM serves no routed architecture yet, the twin
    also brings an engine that does (the stand-in's program behind the
    engine's public surface, with take_decisions), as a driver and a
    traffic kind of its own around the serve driver; a routed
    configuration that the program serves brings neither."""
    root, before = _copy_of_the_benchmark(tmp_path)
    bench = spec.load_benchmark()
    standin = spec.ROOT / "tests" / "benchmark"
    added = ("models/routed_standin.py",
             "models/routed_standin_program.py",
             "reference/routed_standin_plain.py",
             "configs/routed-standin-serve-1chip.json",
             "drivers/serve_closed_standin.py",
             "traffic/batch-offline-standin.json")
    for relative in added:
        assert not (root / "benchmark" / relative).exists()
        shutil.copy(standin / relative, root / "benchmark" / relative)
    config = json.load(open(
        root / "benchmark/configs/routed-standin-serve-1chip.json"))
    assert config["check"]["slack_from"] > 0
    assert config["check"]["control"]
    assert "routing_rejected_share" in config["check"]["limits"]
    metric = json.load(open(
        root / "benchmark/layer_metrics/decode_step_p50_ms.batch.json"))
    metric["name"] = "decode_step_p50_ms.routed"
    (root / "benchmark/layer_metrics/decode_step_p50_ms.routed.json"
     ).write_text(json.dumps(metric))
    bench["configs"].append(
        {"name": "routed-standin-serve-1chip", "source": config["source"],
         "file": "benchmark/configs/routed-standin-serve-1chip.json",
         "reduced": [], "why": "a routed stand-in"})
    bench["workloads"].append(
        {"name": "routed.batch-offline",
         "config": "routed-standin-serve-1chip",
         "traffic": "batch-offline-standin", "chips": 1,
         "why": "a routed model on the closed loop that is there"})
    for entry in bench["end_to_end"]:
        if entry["name"] == "serve_tokens_per_s":
            entry["workloads"].append("routed.batch-offline")
    bench["per_layer"].append(
        {"name": "decode_step_p50_ms.routed", "unit": "ms",
         "better": "lower", "source": "host_clock",
         "layer": metric["layer"], "moves": "serve_tokens_per_s",
         "workloads": ["routed.batch-offline"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    assert _validate(root) == []
    done = _python(
        root, "benchmark/run.py", "--workload", "routed.batch-offline",
        "--seed", str(2**31 + 27), "--seconds", "3", "--trace", "0",
        "--rehearse-tiny",
        JAX_COMPILATION_CACHE_DIR=str(root / ".jax_compile_cache"))
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [l for l in done.stdout.splitlines() if l.strip()]
    result = json.loads(lines[-1])
    assert result["correct"] is True, done.stdout[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    limits = config["check"]["limits"]
    for name in ("gap_tail_mean", "routing_rejected_share"):
        assert any(l.startswith(f"check {name}: ") and
                   f"(limit <= {limits[name]!r}) ok" in l
                   for l in lines), name
        assert result["check"][name]["limit"] == limits[name]
        assert result["check"][name]["value"] <= limits[name]
    told = next(l for l in lines if "the timed path's own choices" in l)
    assert "positions_unrecorded 0 of" in told
    assert "routing_flip_share" in told and "slack_max" in told
    # no file that was there has changed
    assert all(p.read_bytes() == data for p, data in before.items())


@pytest.mark.parametrize("declared,complaint", [
    ("[('a', 4, 64), ('a', 4, 64)]", "one twice"),
    ("[]", "gives no layer"),
    ("[('a', 4)]", "has to give"),
    ("[('a', 4, 4)]", "has to give"),
    ("[('a b', 4, 64)]", "has to give"),
    ("7", "has to give"),
    ("[('a', 4, 64)]", "check.slack_from is not a number"),
])
def test_validate_reports_a_malformed_declaration_of_decisions(
        tmp_path, declared, complaint):
    """A module MAY declare decisions; one that does so in another
    shape, or whose configuration does not say from where a slack is
    rejected and how large a share may be, is reported."""
    root, _before = _copy_of_the_benchmark(tmp_path)
    source = (root / "benchmark/models/dense_mha.py").read_text()
    (root / "benchmark/models/declaring.py").write_text(
        source + f"\n\ndef decision_layers(config, dims):\n"
                 f"    return {declared}\n")
    path = root / spec.load_benchmark()["configs"][0]["file"]
    config = json.load(open(path))
    config["model_module"] = "declaring"
    path.write_text(json.dumps(config))
    problems = spec.validate(root)
    assert problems and all(complaint in p or "routing_rejected" in p
                            for p in problems), problems
    assert any(complaint in p for p in problems)


@pytest.mark.parametrize("fault,complaint", [
    ("no_key", "no model_module"),
    ("no_module", "models/nowhere.py not found"),
    ("no_function", "lacks ['teacher_forced_logits']"),
])
def test_validate_reports_a_configuration_without_its_model(
        tmp_path, fault, complaint):
    root, _before = _copy_of_the_benchmark(tmp_path)
    path = root / spec.load_benchmark()["configs"][0]["file"]
    config = json.load(open(path))
    if fault == "no_key":
        del config["model_module"]
    elif fault == "no_module":
        config["model_module"] = "nowhere"
    else:
        config["model_module"] = "lacking"
        source = (root / "benchmark/models/dense_mha.py").read_text()
        (root / "benchmark/models/lacking.py").write_text(
            source.replace("def teacher_forced_logits(",
                           "def no_such_function("))
    path.write_text(json.dumps(config))
    problems = spec.validate(root)
    assert len(problems) == 1 and complaint in problems[0], problems
    with pytest.raises(spec.SpecError):
        spec.load_model(config, root)


ARCHITECTURE = re.compile(
    r"hidden_size|num_attention_heads|intermediate_size|q_proj|"
    r"gate_proj|TransformerConfig|plain\.")
OF_A_MODEL = ("models", "reference", "configs")


def _benchmark_sources():
    for path in sorted((spec.ROOT / "benchmark").rglob("*")):
        relative = path.relative_to(spec.ROOT / "benchmark")
        if path.is_file() and "__pycache__" not in relative.parts \
                and relative.parts[0] != "testdata":
            yield relative, path.read_text(encoding="utf-8")


def test_only_a_models_own_files_name_an_architecture():
    named = [str(relative) for relative, text in _benchmark_sources()
             if relative.parts[0] not in OF_A_MODEL
             and ARCHITECTURE.search(text)]
    assert named == []
    # ... and the ones that should, do (the pattern still bites)
    assert ARCHITECTURE.search(
        (spec.ROOT / "benchmark/models/dense_mha.py").read_text())


def test_nothing_reads_a_private_name_off_the_engine():
    private = re.compile(r"\bengine\._\w+")
    found = [(str(relative), private.findall(text))
             for relative, text in _benchmark_sources()
             if private.search(text)]
    assert found == []

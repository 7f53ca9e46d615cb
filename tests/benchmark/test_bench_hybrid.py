"""What the hybrid state-space / attention / routed-expert
configuration adds to the benchmark beside its model module and
reference (those are tested, as every configuration's, by
test_bench_flops_and_kernels, test_bench_reference and
test_bench_rehearsal): its file's published widths and cut, the
parameter arithmetic of its share, the decode step's required bytes by
hand, the two readers of the engine's expert counters, and the reader
that times a whole step program on the device trace's program line."""

import json

import pytest

from benchmark import flops, spec

BENCH = spec.load_benchmark()
CONFIG = "nemotron-3-nano-30b-a3b-serve-1chip"
CELL = "nemotron3nano.batch-offline"


def _load(relative):
    return spec.load_module(spec.ROOT, BENCH, relative)


@pytest.fixture(scope="module")
def sized():
    config = spec.load_config(CONFIG)
    module = spec.load_model(config)
    return config, module, module.dims(config)


def test_the_file_states_the_published_widths_uncut(sized):
    config, _module, dims = sized
    assert config["hidden_size"] == 2688
    assert (config["mamba_num_heads"], config["mamba_head_dim"],
            config["n_groups"], config["ssm_state_size"],
            config["conv_kernel"]) == (64, 64, 8, 128, 4)
    assert (config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"]) == \
        (32, 2, 128)
    assert (config["num_experts_per_tok"],
            config["moe_intermediate_size"],
            config["moe_shared_expert_intermediate_size"],
            config["routed_scaling_factor"]) == (6, 1856, 3712, 2.5)
    assert dims["d_inner"] == 4096 and dims["conv_dim"] == 6144
    # the router keeps its published width; 64 of its experts are here
    assert dims["n_router"] == config["published"]["n_routed_experts"] \
        == config["share"]["experts_of"] == 128
    assert dims["experts_held"] == 64 and dims["first_expert"] == 0
    assert config["share"]["chips_sharing_a_layer"] == 2


def test_the_cut_is_depth_experts_held_and_vocabulary(sized):
    config, module, dims = sized
    published = config["published"]
    assert config["reduced"] == list(published)
    assert published["hybrid_override_pattern"].startswith(
        config["hybrid_override_pattern"])
    assert len(published["hybrid_override_pattern"]) == \
        published["num_hidden_layers"] == 52
    assert dims["n_kind"] == {"ssm": 7, "attn": 2, "experts": 7}
    assert published["vocab_size"] == 2 * config["vocab_size"] == \
        config["share"]["vocab_rows_of"]
    # the guide's floors: four blocks and more, 8 experts and more, an
    # eighth of the vocabulary and more
    assert dims["experts_held"] >= 8 and \
        8 * dims["vocab"] >= published["vocab_size"]
    assert [name for name, _k, _n in module.decision_layers(
        config, dims)] == [f"layer_{i}" for i in (1, 3, 6, 8, 10, 13, 15)]
    assert {(k, n) for _name, k, n in module.decision_layers(
        config, dims)} == {(6, 128)}
    for key in ("assumed", "departures", "deployment", "sizing",
                "precision"):
        assert config[key]
    assert any("positional" in line for line in config["assumed"])
    assert any("prefix" in line for line in config["departures"])
    assert "float32" in config["precision"]["ssm_state"]


def test_the_shares_arithmetic_is_the_issues(sized):
    _config, module, dims = sized
    m = 2688 * (4096 + 6144 + 64) + 4096 * 2688 + 4 * 6144 \
        + 6144 + 3 * 64 + 4096 + 2688
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256 + 2688
    expert = 2 * 2688 * 1856
    e = 64 * expert + 2 * 2688 * 3712 + 2688 * 128 + 128 + 2688
    count = flops.param_count(module.param_leaves(dims))
    assert count == 7 * m + 2 * attn + 7 * e + 2 * 65536 * 2688 + 2688
    assert round(m / 1e6, 2) == 38.74 and round(e / 1e6, 1) == 658.9
    assert round(count / 1e9, 3) == 5.283       # 10.57 GB in bfloat16
    # what the work function reads of them (no norm scales or biases)
    assert dims["params"]["expert"] == expert
    assert dims["params"]["ssm"] == m - (6144 + 3 * 64 + 4096 + 2688)
    assert dims["slot_state_bytes"] == 7 * (64 * 64 * 128 * 4
                                            + 3 * 6144 * 2)
    assert dims["kv_bytes_per_token"] == 2048


def test_decode_step_work_by_hand(sized):
    _config, _module, dims = sized
    step = _load("kernels/decode_step.py").step_work
    always = (7 * dims["params"]["ssm"] + 2 * dims["params"]["attn"]
              + 7 * dims["params"]["experts_always"]
              + dims["params"]["head"])
    idle = step(dims, slots=0, tokens=0, hit=0, pairs=0)
    assert idle == {"flops": 0.0, "bytes": 2.0 * always}
    full = step(dims, slots=96, tokens=96 * 600, hit=7 * 64,
                pairs=96 * 6 * 7 / 2)
    assert full["bytes"] == pytest.approx(
        2 * always + 2 * 448 * 9977856 + 2 * 96 * 2688
        + 2 * 96 * dims["slot_state_bytes"] + 96 * 600 * 2048)
    # the issue's reckoning: 10.2 GB of weights of which 8.9 GB held
    # experts, 2.7 GB of state read and written
    assert round(2 * 448 * 9977856 / 1e9, 1) == 8.9
    assert round(2 * 96 * dims["slot_state_bytes"] / 1e9, 1) == 2.9
    assert 12.5e9 < full["bytes"] < 14e9
    assert full["flops"] == 2.0 * (always * 96 + 9977856 * 2016)
    # an expert nobody chose is not read
    fewer = step(dims, slots=96, tokens=96 * 600, hit=400, pairs=2016)
    assert full["bytes"] - fewer["bytes"] == 2.0 * 48 * 9977856


def _rows(tmp_path, rows, window=(100.0, 151.0)):
    with open(tmp_path / "loadgen.json", "w") as fh:
        json.dump({"window_start": window[0],
                   "window_s": window[1] - window[0]}, fh)
    with open(tmp_path / "spans.jsonl", "w") as fh:
        for i, attrs in enumerate(rows):
            base = {"mono_start": 101.0 + i, "prefills": 0,
                    "live_tokens": 1000, "experts_held": 448}
            fh.write(json.dumps({"kind": "serve_step", "start": 0.0,
                                 "end": 0.02,
                                 "attrs": {**base, **attrs}}) + "\n")


ROWS = [{"expert_pairs_chosen": 4032, "expert_pairs_here": 2016,
         "experts_hit": 440},
        {"expert_pairs_chosen": 2016, "expert_pairs_here": 1040,
         "experts_hit": 400},
        # a call that landed no decode step (a prefill after a settle)
        {"expert_pairs_chosen": 0, "expert_pairs_here": 0,
         "experts_hit": 0},
        # outside the window
        {"mono_start": 99.0, "expert_pairs_chosen": 4032,
         "expert_pairs_here": 4032, "experts_hit": 448}]


@pytest.mark.parametrize("value,expected", [
    ("rows_per_expert", (2016 / 448 + 1040 / 448) / 2),
    ("routed_here_pct", 100.0 * 3056 / 6048)])
def test_expert_counters_by_hand(tmp_path, value, expected):
    _rows(tmp_path, ROWS)
    reader = _load("layer_metrics/readers/expert_rows.py")
    obs = {"out_dir": tmp_path}
    assert reader.read(obs, {"value": value}) == pytest.approx(expected)
    # 4.5 rows an expert is a full batch of 96 with an even router
    assert 96 * 6 / 128 == 4.5 == 2016 / 448


def test_expert_counters_read_none_without_the_programs_counters(
        tmp_path):
    """The parent commit writes rows without these attrs, and a run
    without rows at all: the metric is left out, nothing raises."""
    reader = _load("layer_metrics/readers/expert_rows.py")
    assert reader.read({"out_dir": tmp_path},
                       {"value": "rows_per_expert"}) is None
    assert reader.read({}, {"value": "routed_here_pct"}) is None
    _rows(tmp_path, [{"slots_active": 3}, {"slots_active": 4}])
    for value in ("rows_per_expert", "routed_here_pct"):
        assert reader.read({"out_dir": tmp_path},
                           {"value": value}) is None


def _trace():
    with open(spec.ROOT / "benchmark/testdata/small_trace.json") as fh:
        return json.load(fh)


def test_a_step_program_is_timed_on_the_program_line(sized, tmp_path):
    """The recorded trace has one launch of jit__decode_step on its
    first device: its duration against the bytes one step must move."""
    _config, _module, dims = sized
    reader = _load("layer_metrics/readers/program_roofline.py")
    trace = _trace()
    seconds, calls = reader.program_seconds(trace, "_decode_step")
    event = next(e for line in trace["planes"][0]["lines"]
                 if line["name"] == "XLA Modules"
                 for e in line["events"])
    assert calls == 1 and seconds == pytest.approx(event[2] / 1e9)
    assert reader.program_seconds(trace, "_prefill_paged") == (0.0, 0)
    _rows(tmp_path, ROWS[:2])
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    obs = {"profile": {"trace": trace, "started": 100.0,
                       "stopped": 110.0},
           "peaks": peaks, "dims": dims, "out_dir": tmp_path}
    params = {"kernel": "decode_step", "program_pattern": "_decode_step"}
    work = _load("kernels/decode_step.py")
    one = work.step_work(dims, slots=(4032 + 2016) / 2 / 42,
                         tokens=1000, hit=420, pairs=1528)
    assert work.work(obs, {"program": 1}) == pytest.approx(one)
    assert reader.read(obs, params) == pytest.approx(
        100.0 * one["bytes"] / 819e9 / seconds)
    assert obs["roofline_bound"] == {"decode_step": "memory"}


def test_the_roofline_reads_none_where_there_is_nothing(sized, tmp_path):
    _config, _module, dims = sized
    reader = _load("layer_metrics/readers/program_roofline.py")
    params = {"kernel": "decode_step", "program_pattern": "_decode_step"}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert reader.read({"profile": None, "peaks": peaks}, params) is None
    # a program whose rows lack the counters (the parent commit)
    _rows(tmp_path, [{"slots_active": 3}])
    obs = {"profile": {"trace": _trace(), "started": 100.0,
                       "stopped": 110.0},
           "peaks": peaks, "dims": dims, "out_dir": tmp_path}
    assert reader.read(obs, params) is None
    # no program of that name in the trace
    assert reader.read(obs, dict(params, program_pattern="nothing")) \
        is None


def test_the_cell_lists_its_metrics_and_reports_tokens_per_second():
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "setup_s"}
    suffix = ".nemotron3nano"
    assert {m["name"] for m in cell.per_layer} == {
        name + suffix for name in (
            "decode_step_p50_ms", "batch_occupancy_pct", "hbm_peak_pct",
            "idle_step_loop_pct", "idle_admit_pct",
            "prefill_step_share_pct", "expert_rows_per_expert",
            "routed_here_pct", "decode_step_roofline",
            # the layer that bounds the cell today (the host's work a
            # call), and the pool the engine step runs on
            "step_host_p50_ms", "kv_pages_peak_pct")}
    assert all(m["moves"] == "serve_tokens_per_s"
               for m in cell.per_layer)
    assert cell.traffic["clients"] == cell.config["engine"]["num_slots"]
    # the accepted cells' lists were appended to, not changed
    for metric in BENCH["per_layer"]:
        if not metric["name"].endswith(suffix):
            assert CELL not in metric["workloads"]

"""What the delta-rule / gated attention / gated-expert configuration
adds to the benchmark beside its model module and reference (those are
tested, as every configuration's, by test_bench_flops_and_kernels,
test_bench_reference and test_bench_rehearsal): its file's published
widths and cut, the parameter arithmetic of its share, the decode
step's and the delta blocks' required bytes by hand, and the reader
that times some operations of one step program."""

import json

import pytest

from benchmark import flops, spec

BENCH = spec.load_benchmark()
CONFIG = "solar-open2-250b-serve-1chip"
CELL = "solaropen2.batch-offline"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
BENCH_METRIC = spec.layer_metric_file("delta_step_roofline.solaropen2")


def _load(relative):
    return spec.load_module(spec.ROOT, BENCH, relative)


@pytest.fixture(scope="module")
def sized():
    config = spec.load_config(CONFIG)
    module = spec.load_model(config)
    return config, module, module.dims(config)


def test_the_file_states_the_published_widths_uncut(sized):
    config, _module, dims = sized
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"]) == \
        (4096, 64, 8, 128)
    assert config["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert (config["num_experts_per_tok"],
            config["moe_intermediate_size"], config["n_shared_experts"],
            config["routed_scaling_factor"],
            config["intermediate_size"]) == (8, 1280, 1, 1, 10240)
    assert (config["use_rope"], config["use_gqa_gate"],
            config["kda_use_full_proj"], config["kda_allow_neg_eigval"],
            config["norm_topk_prob"]) == (False, True, False, True, True)
    assert dims["d_inner"] == 8192 and dims["d_shared"] == 1280
    # the router keeps its published width; 40 of its experts are here
    assert dims["n_router"] == config["published"]["n_routed_experts"] \
        == config["share"]["experts_of"] == 320
    assert dims["experts_held"] == 40 and dims["first_expert"] == 0
    assert config["share"]["chips_sharing_a_layer"] == 8
    # every number of the catalog entry's config, under the same key,
    # but the four that are cut
    published = {
        "partial_rotary_factor": 1, "hidden_size": 4096,
        "num_hidden_layers": 48, "num_attention_heads": 64,
        "head_dim": 128, "num_key_value_heads": 8,
        "vocab_size": 196608, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "max_position_embeddings": 1048576,
        "first_k_dense_replace": 0, "gqa_interval": 3,
        "n_routed_experts": 320, "n_shared_experts": 1,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    for key, value in published.items():
        assert config["published"].get(key, config[key]) == value, key


def test_the_cut_is_depth_experts_held_and_vocabulary(sized):
    config, module, dims = sized
    published = config["published"]
    assert config["reduced"] == list(published) == [
        "num_hidden_layers", "gqa_layers", "n_routed_experts",
        "vocab_size"]
    # one whole period of the published 1 : 3, as two blocks a layer
    assert published["gqa_layers"] == list(range(0, 48, 4))
    assert config["gqa_layers"] == [0]
    assert dims["kinds"] == ("attn", "experts") + ("delta", "experts") * 3
    assert dims["n_kind"] == {"delta": 3, "attn": 1, "experts": 4}
    assert 8 * config["vocab_size"] == published["vocab_size"] == \
        config["share"]["vocab_rows_of"]
    # the guide's floors: a whole period of four layers, 8 experts and
    # more, an eighth of the vocabulary
    assert config["num_hidden_layers"] >= 4
    assert dims["experts_held"] >= 8
    assert [(name, k, n) for name, k, n in module.decision_layers(
        config, dims)] == [(f"layer_{i}", 8, 320) for i in (1, 3, 5, 7)]
    for key in ("assumed", "departures", "deployment", "sizing",
                "precision"):
        assert config[key]
    assert any("sigmoid" in line for line in config["assumed"])
    assert any("prefix" in line for line in config["departures"])
    assert "float32" in config["precision"]["delta_state"]
    assert {"decisions": {"reroute_share": 0.01}} in \
        config["check"]["control"]
    assert {"delta_state_dtype": "bfloat16"} in config["check"]["control"]


def test_the_shares_arithmetic_is_the_issues(sized):
    _config, module, dims = sized
    delta = 3 * 4096 * 8192 + 8192 * 4096 \
        + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64 + 3 * 4 * 8192
    attn = 2 * 4096 * 8192 + 2 * 4096 * 1024 + 8192 * 4096
    expert = 3 * 4096 * 1280
    routed = 40 * expert + expert + 4096 * 320
    assert (round(delta / 1e6, 1), round(attn / 1e6, 1),
            round(routed / 1e6, 1)) == (137.7, 109.1, 646.2)
    # leaves the issue's round numbers leave out: norm scales, A_log,
    # dt_bias, the head norm's scale, the selection bias
    small = 8 * 4096 + 4096 + 3 * (64 + 8192 + 128) + 4 * 320
    count = flops.param_count(module.param_leaves(dims))
    assert count == 3 * delta + attn + 4 * routed \
        + 2 * 24576 * 4096 + small
    assert round(count / 1e9, 2) == 3.31          # 6.62 GB in bfloat16
    assert dims["params"] == {
        "delta": delta, "attn": attn, "expert": expert,
        "experts_always": expert + 4096 * 320, "head": 24576 * 4096}
    # a slot: three states of 64 x 128 x 128 float32 and three tails
    # of 3 rows x 24,576 channels; K/V of the one attention layer
    assert dims["slot_state_bytes"] == 3 * (4 * 2 ** 20 + 3 * 24576 * 2)
    assert round(96 * dims["slot_state_bytes"] / 1e9, 2) == 1.25
    assert dims["kv_bytes_per_token"] == 4096


def test_decode_step_work_by_hand(sized):
    _config, _module, dims = sized
    step = _load("kernels/decode_step_kinds.py").step_work
    always = (3 * dims["params"]["delta"] + dims["params"]["attn"]
              + 4 * dims["params"]["experts_always"]
              + dims["params"]["head"])
    idle = step(dims, slots=0, tokens=0, hit=0, pairs=0)
    assert idle == {"flops": 0.0, "bytes": 2.0 * always}
    full = step(dims, slots=96, tokens=96 * 600, hit=146,
                pairs=96 * 8 * 4 / 8)
    assert full["bytes"] == pytest.approx(
        2 * always + 2 * 146 * 15728640 + 2 * 96 * 4096
        + 2 * 96 * dims["slot_state_bytes"] + 96 * 600 * 4096)
    # the issue's reckoning: held experts 5.2 GB when all 160 are hit,
    # the delta state read and written 2.4 GB (2.5 with the tails),
    # mixers 1.0 GB, head and K/V 0.4 GB
    assert round(2 * 160 * 15728640 / 1e9, 1) == 5.0
    assert round(2 * 96 * dims["slot_state_bytes"] / 1e9, 1) == 2.5
    assert round(2 * (3 * dims["params"]["delta"]
                      + dims["params"]["attn"]) / 1e9, 1) == 1.0
    assert 8.5e9 < full["bytes"] < 9e9
    assert full["flops"] == 2.0 * (always * 96 + 15728640 * 384)
    # an expert nobody chose is not read
    fewer = step(dims, slots=96, tokens=96 * 600, hit=140, pairs=384)
    assert full["bytes"] - fewer["bytes"] == 2.0 * 6 * 15728640
    # the same function counts the state-space stack as its own does
    other = spec.load_config("nemotron-3-nano-30b-a3b-serve-1chip")
    theirs = spec.load_model(other).dims(other)
    seen = dict(slots=96, tokens=5e4, hit=400, pairs=2016)
    assert step(theirs, **seen) == pytest.approx(
        _load("kernels/decode_step.py").step_work(theirs, **seen))


def test_delta_step_work_by_hand(sized):
    _config, _module, dims = sized
    step = _load("kernels/delta_step.py").step_work
    weights = 3 * dims["params"]["delta"]
    assert step(dims, 0) == {"flops": 0.0, "bytes": 2.0 * weights}
    full = step(dims, 96)
    # the state read ONCE and written once
    assert full["bytes"] == 2 * weights + 2 * 96 * dims["slot_state_bytes"]
    assert round(full["bytes"] / 1e9, 2) == 3.33
    assert full["flops"] == (2 * weights + 8 * 3 * 64 * 128 * 128) * 96
    # memory-bound on a v5e: 4.1 ms of bytes against 0.4 ms of operations
    assert full["bytes"] / 819e9 > 9 * full["flops"] / 197e12


def _rows(tmp_path, rows, window=(100.0, 151.0)):
    with open(tmp_path / "loadgen.json", "w") as fh:
        json.dump({"window_start": window[0],
                   "window_s": window[1] - window[0]}, fh)
    with open(tmp_path / "spans.jsonl", "w") as fh:
        for i, attrs in enumerate(rows):
            base = {"mono_start": 101.0 + i, "prefills": 0,
                    "live_tokens": 1000, "experts_held": 160}
            fh.write(json.dumps({"kind": "serve_step", "start": 0.0,
                                 "end": 0.02,
                                 "attrs": {**base, **attrs}}) + "\n")


ROWS = [{"expert_pairs_chosen": 3072, "expert_pairs_here": 384,
         "experts_hit": 150},
        {"expert_pairs_chosen": 1536, "expert_pairs_here": 200,
         "experts_hit": 110},
        # a call that landed no decode step (a prefill after a settle)
        {"expert_pairs_chosen": 0, "expert_pairs_here": 0,
         "experts_hit": 0}]


def _trace():
    """The recorded trace's first device: one launch of
    jit__decode_step from 1,000 to 10,000 ns. Two operations of a
    delta block inside it, one of another kind, a loop that contains,
    and a delta operation of ANOTHER program after it. Events are
    named as the chip names them (my chip run, PR 33): the
    instruction's HLO text, which names a layer only by the program
    ARGUMENTS among its operands."""
    with open(spec.ROOT / "benchmark/testdata/small_trace.json") as fh:
        trace = json.load(fh)
    plane = trace["planes"][0]
    modules = next(line for line in plane["lines"]
                   if line["name"] == "XLA Modules")
    modules["events"].append(["jit__prefill_paged(2)", 20000, 5000])
    ops = next(line for line in plane["lines"]
               if line["name"] == "XLA Ops")
    name = ('%multiply_reduce_fusion.{} = f32[96,64,128]{{2,1,0}} fusion('
            'f32[96,64,128,128]{{3,2,1,0}} %{}__layer_{}____{}____{}__.1, '
            'f32[96,64,128]{{2,1,0}} %fusion.59), kind=kLoop')
    ops["events"] += [
        [name.format(7, "cache", 2, "delta", "delta_state"), 4500, 700],
        [name.format(8, "params", 4, "delta", "qkv_proj____kernel"),
         5200, 300],
        [name.format(9, "params", 3, "experts", "experts_up"), 5500, 400],
        ['%while.3 = () while((s32[], f32[96,64,128,128]) '
         '%cache__layer_2____delta____delta_state__.1)', 4500, 1000],
        # the same arguments in another program's launch
        [name.format(7, "cache", 2, "delta", "delta_state"), 21000,
         2000]]
    return trace


DELTA = r"(params|cache)__layer_\d+____delta____"


def test_operations_are_kept_by_name_and_by_the_program_that_ran_them(
        sized, tmp_path):
    _config, _module, dims = sized
    reader = _load("layer_metrics/readers/ops_in_program_roofline.py")
    trace = _trace()
    delta = DELTA
    assert BENCH_METRIC["params"]["event_pattern"] == DELTA
    assert reader.seconds_inside(trace, "_decode_step", delta) == \
        (pytest.approx(1000e-9), 1)
    assert reader.seconds_inside(trace, "_prefill_paged", delta) == \
        (pytest.approx(2000e-9), 1)
    assert reader.seconds_inside(trace, "_decode_step",
                                 r"__layer_\d+____experts____")[0] == \
        pytest.approx(400e-9)
    assert reader.seconds_inside(trace, "nothing", delta) == (0.0, 0)
    _rows(tmp_path, ROWS)
    obs = {"profile": {"trace": trace, "started": 100.0,
                       "stopped": 110.0},
           "peaks": PEAKS, "dims": dims, "out_dir": tmp_path}
    params = {"kernel": "delta_step", "program_pattern": "_decode_step",
              "event_pattern": delta}
    # two rows landed a step: 96 and 48 seated slots, 72 in the mean
    kinds = _load("kernels/decode_step_kinds.py")
    assert kinds.mean_step(obs) == pytest.approx(
        {"slots": 72.0, "tokens": 1000.0, "hit": 130.0, "pairs": 292.0})
    one = _load("kernels/delta_step.py").step_work(dims, 72.0)
    assert reader.read(obs, params) == pytest.approx(
        100.0 * one["bytes"] / 819e9 / 1000e-9)
    assert obs["roofline_bound"] == {"delta_step": "memory"}
    # the whole step's share by the reader that was there, with the
    # work function that takes any list of kinds
    whole = _load("layer_metrics/readers/program_roofline.py").read(
        obs, {"kernel": "decode_step_kinds",
              "program_pattern": "_decode_step"})
    assert whole == pytest.approx(
        100.0 * kinds.step_work(dims, 72.0, 1000.0, 130.0, 292.0)[
            "bytes"] / 819e9 / 9000e-9)


def test_the_reader_reads_none_where_there_is_nothing(sized, tmp_path):
    """As on the parent commit: no such operation names, no rows with
    the counters, or no profile at all; nothing raises."""
    _config, _module, dims = sized
    reader = _load("layer_metrics/readers/ops_in_program_roofline.py")
    params = {"kernel": "delta_step", "program_pattern": "_decode_step",
              "event_pattern": DELTA}
    assert reader.read({"profile": None, "peaks": PEAKS}, params) is None
    assert reader.read({}, params) is None
    with open(spec.ROOT / "benchmark/testdata/small_trace.json") as fh:
        bare = json.load(fh)
    _rows(tmp_path, ROWS)
    obs = {"profile": {"trace": bare, "started": 100.0,
                       "stopped": 110.0},
           "peaks": PEAKS, "dims": dims, "out_dir": tmp_path}
    assert reader.read(obs, params) is None
    # ... and with the operations but rows that lack the counters
    _rows(tmp_path, [{"slots_active": 3}])
    obs = {"profile": {"trace": _trace(), "started": 100.0,
                       "stopped": 110.0},
           "peaks": PEAKS, "dims": dims, "out_dir": tmp_path}
    assert reader.read(obs, params) is None


def test_the_cell_lists_its_metrics_and_reports_tokens_per_second():
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "setup_s"}
    suffix = ".solaropen2"
    assert {m["name"] for m in cell.per_layer} == {
        name + suffix for name in (
            "decode_step_p50_ms", "step_host_p50_ms",
            "batch_occupancy_pct", "hbm_peak_pct", "kv_pages_peak_pct",
            "idle_step_loop_pct", "idle_admit_pct",
            "prefill_step_share_pct", "expert_rows_per_expert",
            "routed_here_pct", "decode_step_roofline",
            "delta_step_roofline")}
    assert all(m["moves"] == "serve_tokens_per_s"
               for m in cell.per_layer)
    assert cell.traffic["clients"] == cell.config["engine"]["num_slots"]
    # the same traffic file as the two cells before it, unchanged
    assert cell.traffic_name == "batch-offline"
    # the accepted cells' lists were appended to, not changed
    for metric in BENCH["per_layer"]:
        if not metric["name"].endswith(suffix):
            assert CELL not in metric["workloads"]

"""What the sliding-window / full attention configuration over routed
ReGLU experts adds to the benchmark beside its model module and
reference (those are tested, as every configuration's, by
test_bench_flops_and_kernels, test_bench_reference and
test_bench_rehearsal): its file's published widths and cut, the
parameter arithmetic, the traffic file letter for letter, the two
work functions by hand, the reader of the window counters, and that
every entry it brought lists its one cell alone."""

import json

import pytest

from benchmark import flops, spec

BENCH = spec.load_benchmark()
CONFIG = "smallthinker-21b-a3b-serve-1chip"
CELL = "smallthinker.mixedctx-offline"
SOURCE = ("https://huggingface.co/PowerInfer/"
          "SmallThinker-21BA3B-Instruct/blob/main/config.json")


def _load(relative):
    return spec.load_module(spec.ROOT, BENCH, relative)


@pytest.fixture(scope="module")
def sized():
    config = spec.load_config(CONFIG)
    module = spec.load_model(config)
    return config, module, module.dims(config)


def test_the_file_states_the_published_widths_uncut(sized):
    config, _module, dims = sized
    assert config["source"] == SOURCE
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"]) == \
        (2560, 28, 4, 128)
    assert (config["moe_num_primary_experts"],
            config["moe_num_active_primary_experts"],
            config["moe_ffn_hidden_size"]) == (64, 6, 768)
    assert (config["sliding_window_size"], config["rope_theta"],
            config["max_position_embeddings"], config["vocab_size"],
            config["rms_norm_eps"]) == (4096, 1500000, 16384, 151936,
                                        1e-6)
    assert config["tie_word_embeddings"] is False
    assert config["rope_scaling"] is None
    # every expert is held, the vocabulary is whole
    assert dims["experts_held"] == dims["n_router"] == 64
    assert dims["vocab"] == 151936


def test_the_cut_is_depth_alone_two_whole_periods(sized):
    config, module, dims = sized
    published = config["published"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == list(published) == [
        "num_hidden_layers", "rope_layout", "sliding_window_layout"]
    assert entry["source"] == SOURCE
    assert published["num_hidden_layers"] == 52
    assert published["rope_layout"] == \
        published["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert config["num_hidden_layers"] == 8
    assert config["rope_layout"] == config["sliding_window_layout"] \
        == published["rope_layout"][:8]
    assert dims["windows"] == (0, 4096, 4096, 4096) * 2
    assert dims["n_kind"] == {"attn_full": 2, "attn_window": 6,
                              "experts": 8}
    assert dims["kinds"] == ("attn", "experts") * 8
    assert [name for name, _k, _n in module.decision_layers(
        config, dims)] == [f"layer_{i}" for i in range(1, 16, 2)]
    assert {(k, n) for _name, k, n in module.decision_layers(
        config, dims)} == {(6, 64)}
    for key in ("assumed", "deployment", "sizing", "precision",
                "engine_built_through"):
        assert config[key]
    assumed = " ".join(config["assumed"])
    for said in ("before attention", "ReGLU", "shared expert",
                 "q/k norm", "sink", "j > i - 4096", "secondary",
                 "dense", "EOS", "qk_gain"):
        assert said in assumed, said
    assert "ONE chip a layer" in config["deployment"]
    assert config["engine"] == {
        "num_slots": 48, "max_decode_len": 16384, "kv_page_size": 64,
        "kv_num_pages": 6144, "overcommit": False,
        "prefix_cache": True, "sampling": "greedy",
        "speculative": False}
    controls = config["check"]["control"]
    assert {"windows_off": True} in controls
    assert {"decisions": {"reroute_share": 0.01}} in controls


def test_every_stated_control_builds_the_program_it_names(sized):
    """A control's overrides are keyword arguments of program_model:
    the window taken off, or the program's own lower-precision
    switches; the sound program keeps float32 where the file's
    ``precision`` says so."""
    import jax.numpy as jnp
    config, module, dims = sized
    sound = module.program_model(config, dims, config["engine"])
    assert sound.attn_softmax_dtype == jnp.float32
    assert sound.experts.router_dtype == jnp.float32
    assert sound.layer_windows[2] == 4096
    from benchmark import check
    for overrides in check.controls(config["check"]["control"]):
        overrides = {k: v for k, v in overrides.items()
                     if k != "decisions"}
        built = module.program_model(config, dims, config["engine"],
                                     **overrides)
        assert (built != sound) == bool(overrides)
    low = module.program_model(
        config, dims, config["engine"], attn_softmax_dtype="bfloat16",
        router_dtype="bfloat16")
    assert low.attn_softmax_dtype == jnp.bfloat16
    assert low.experts.router_dtype == jnp.bfloat16


def test_the_arithmetic_is_the_issues(sized):
    _config, module, dims = sized
    attn = 2560 * (3584 + 512 + 512) + 3584 * 2560
    router = 2560 * 64
    experts = 64 * 3 * 2560 * 768
    layer = attn + router + experts + 2 * 2560       # two norm scales
    count = flops.param_count(module.param_leaves(dims))
    assert count == 8 * layer + 2 * 151936 * 2560 + 2560
    assert round(attn / 1e6, 2) == 20.97
    assert round(experts / 1e6, 2) == 377.49
    assert round(layer / 1e6, 1) == 398.6
    assert round(count / 1e9, 3) == 3.967            # 7.93 GB bfloat16
    assert dims["params"] == {"attn": attn, "experts_always": router,
                              "expert": 3 * 2560 * 768,
                              "head": 2560 * 151936}
    # K and V of 4 heads of 128 in 2 bytes: 2 KiB a token a layer
    assert dims["kv_bytes_per_token_layer"] == 2048
    # the pool (2 full layers) and the rings (6 window layers)
    assert 6144 * 64 * 2048 * 2 == round(1.5 * 2 ** 30)
    assert round(48 * 65 * 64 * 2048 * 6 / 2 ** 30, 2) == 2.29


def test_the_traffic_is_the_issues_letter_for_letter():
    with open(spec.ROOT / "benchmark/traffic/mixedctx-offline.json") \
            as fh:
        traffic = json.load(fh)
    with open(spec.ROOT / "benchmark/traffic/batch-offline.json") as fh:
        sibling = json.load(fh)
    assert set(traffic) == set(sibling)      # the keys the sibling has
    small = {k: v for k, v in traffic.items()
             if k not in ("what", "rehearse_tiny")}
    assert small == {
        "kind": "serve-closed", "clients": 48, "client_stagger_s": 0.05,
        "prompt_tokens": {"median": 4096, "sigma": 0.7, "min": 512,
                          "max": 12288},
        "output_tokens": {"median": 512, "sigma": 0.5, "min": 128,
                          "max": 1024},
        "shared_prefix_tokens": 0, "path_seed": 40,
        "pool_requests": 512, "lead_in_s": 15, "trace_slice_s": 4}
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "mixedctx-offline"
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "setup_s"}


NEW = ("gqa_paged_decode_roofline", "decode_step_roofline",
       "window_attended_pct", "window_pages_peak_pct")
BY_READERS_THAT_WERE_THERE = (
    "decode_launch_p50_ms", "decode_step_p50_ms", "step_host_p50_ms",
    "host_behind_pct", "batch_occupancy_pct", "hbm_peak_pct",
    "kv_pages_peak_pct", "prefill_device_share_pct",
    "prefill_ms_per_ktoken", "prefill_padding_pct",
    "expert_rows_per_expert",
    # these layers run in the cell too (the review): the device's idle
    # share by engine phase, and the engine thread's time in prefills
    "idle_step_loop_pct", "idle_admit_pct", "prefill_step_share_pct")


def test_every_entry_it_brought_lists_its_cell_alone():
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.per_layer} == {
        f"{name}.smallthinker"
        for name in NEW + BY_READERS_THAT_WERE_THERE}
    for metric in cell.per_layer:
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_tokens_per_s"
    # the accepted cells read none of them, and nothing they read
    # changed its list
    for other in BENCH["workloads"]:
        if other["name"] != CELL:
            assert not any(
                m["name"].endswith(".smallthinker")
                for m in spec.load_cell(other["name"]).per_layer)
    rooflines = [m for m in cell.per_layer if "roofline" in m["name"]]
    assert {m["unit"] for m in rooflines} == {"%"}
    definition = spec.layer_metric_file(
        "gqa_paged_decode_roofline.smallthinker")
    assert definition["params"]["kernel"] == "paged_decode_windowed"
    import re

    from batch_shipyard_tpu.ops import paged_attention as pa
    pattern = re.compile(definition["params"]["event_patterns"]["decode"])
    # the grouped kernel's device events are named apart from the MHA
    # kernel's
    assert pattern.search(f"%{pa.GQA_KERNEL_NAME}.3 = bf16[48,32,128]"
                          f"{{2,1,0}} custom-call(...)")
    assert not pattern.search(
        "%attn._decode_attend_paged.1 = bf16[48,1,4096]{2,1,0} "
        "custom-call(...)")


def test_windowed_paged_decode_work_by_hand(sized):
    _config, _module, dims = sized
    work = _load("kernels/paged_decode_windowed.py")
    one = work.call_work(tokens=1000, slots=48, n_heads=28,
                         n_kv_heads=4, d_head=128)
    # K and V rows of 4 x 128 lanes in 2 bytes a key; queries in,
    # outputs out
    assert one["bytes"] == 2 * 1000 * 512 * 2 + 2 * 48 * 3584 * 2
    assert one["flops"] == 4 * 1000 * 28 * 128


def _rows(tmp_path, rows, window=(100.0, 151.0)):
    with open(tmp_path / "loadgen.json", "w") as fh:
        json.dump({"window_start": window[0],
                   "window_s": window[1] - window[0]}, fh)
    with open(tmp_path / "spans.jsonl", "w") as fh:
        for i, attrs in enumerate(rows):
            base = {"mono_start": 101.0 + i, "prefills": 0}
            fh.write(json.dumps({"kind": "serve_step", "start": 0.0,
                                 "end": 0.02,
                                 "attrs": {**base, **attrs}}) + "\n")


ROWS = [{"slots_active": 48, "kv_tokens_full": 250000,
         "kv_tokens_window": 160000, "expert_pairs_chosen": 2304,
         "expert_pairs_here": 2304, "experts_hit": 500,
         "window_pages_in_use": 2500, "window_pages_total": 3120},
        {"slots_active": 46, "kv_tokens_full": 230000,
         "kv_tokens_window": 150000, "expert_pairs_chosen": 2208,
         "expert_pairs_here": 2208, "experts_hit": 490,
         "window_pages_in_use": 2600, "window_pages_total": 3120},
        # a call that landed no decode step
        {"slots_active": 47, "kv_tokens_full": 240000,
         "kv_tokens_window": 155000, "expert_pairs_chosen": 0,
         "expert_pairs_here": 0, "experts_hit": 0,
         "window_pages_in_use": 2550, "window_pages_total": 3120},
        # outside the window
        {"mono_start": 99.0, "slots_active": 48,
         "kv_tokens_full": 1, "kv_tokens_window": 1,
         "expert_pairs_chosen": 2304, "expert_pairs_here": 2304,
         "experts_hit": 512, "window_pages_in_use": 3120,
         "window_pages_total": 3120}]


def test_the_work_over_a_traced_slice_is_by_the_layers_kind(sized,
                                                            tmp_path):
    _config, _module, dims = sized
    _rows(tmp_path, ROWS)
    obs = {"profile": {"started": 100.0, "stopped": 110.0},
           "dims": dims, "out_dir": tmp_path}
    kernel = _load("kernels/paged_decode_windowed.py")
    # 16 kernel calls = 2 steps of 8 layers: 2 full, 6 window each
    got = kernel.work(obs, {"decode": 16})
    full = kernel.call_work(240000, 47, 28, 4, 128)
    window = kernel.call_work(155000, 47, 28, 4, 128)
    assert got["bytes"] == pytest.approx(
        2 * (2 * full["bytes"] + 6 * window["bytes"]))
    assert got["flops"] == pytest.approx(
        2 * (2 * full["flops"] + 6 * window["flops"]))
    step = _load("kernels/decode_step_windowed.py")
    one = step.step_work(dims, slots=47, hit=495, pairs=2256,
                         full=240000, window=155000)
    assert step.work(obs, {"program": 3}) == pytest.approx(
        {name: 3 * value for name, value in one.items()})
    p = dims["params"]
    always = p["head"] + 8 * p["attn"] + 8 * p["experts_always"]
    assert one["bytes"] == pytest.approx(
        2 * (always + 495 * p["expert"] + 47 * 2560)
        + 2048 * (2 * 240000 + 6 * 155000))
    assert one["flops"] == pytest.approx(
        2 * (always * 47 + p["expert"] * 2256)
        + 4 * 3584 * (2 * 240000 + 6 * 155000))
    # the issue's reckoning of a full step: 6.0 GB of experts, 0.34 GB
    # of attention weights, 0.78 GB of head
    assert round(2 * 512 * p["expert"] / 1e9, 1) == 6.0
    assert round(2 * 8 * p["attn"] / 1e9, 2) == 0.34
    assert round(2 * p["head"] / 1e9, 2) == 0.78


def test_the_window_counters_by_hand(tmp_path):
    _rows(tmp_path, ROWS)
    reader = _load("layer_metrics/readers/rows_ratio.py")
    assert reader.read({"out_dir": tmp_path}, {
        "part": "kv_tokens_window", "whole": "kv_tokens_full",
        "take": "sum"}) == pytest.approx(100 * 465000 / 720000)
    assert reader.read({"out_dir": tmp_path}, {
        "part": "window_pages_in_use", "whole": "window_pages_total",
        "take": "max"}) == pytest.approx(100 * 2600 / 3120)


def test_the_new_readers_read_none_without_the_programs_counters(
        sized, tmp_path):
    """The parent commit writes rows without these attrs, and a run
    without rows at all: each metric is left out, nothing raises."""
    _config, _module, dims = sized
    reader = _load("layer_metrics/readers/rows_ratio.py")
    params = {"part": "kv_tokens_window", "whole": "kv_tokens_full",
              "take": "sum"}
    assert reader.read({"out_dir": tmp_path}, params) is None
    assert reader.read({}, params) is None
    _rows(tmp_path, [{"slots_active": 3, "expert_pairs_chosen": 12,
                      "expert_pairs_here": 12, "experts_hit": 9}])
    assert reader.read({"out_dir": tmp_path}, params) is None
    obs = {"profile": {"started": 100.0, "stopped": 110.0},
           "dims": dims, "out_dir": tmp_path}
    for kernel in ("paged_decode_windowed", "decode_step_windowed"):
        assert _load(f"kernels/{kernel}.py").work(
            obs, {"decode": 8}) is None

"""What the configuration with a leading dense layer, window and full
attention layers with q/k norms over sigmoid-routed experts and a
multi-token-prediction module adds to the benchmark beside its model
module and reference (those are tested, as every configuration's, by
test_bench_flops_and_kernels, test_bench_reference and
test_bench_rehearsal): its file's published widths, cut and share, the
parameter arithmetic, the traffic file letter for letter, the three
work functions by hand, the tree against the program's, the reference's
stack AND module logits against the program's at a small size, the
eight chips' parts of one sparse layer adding up, and that every entry
it brought lists its one cell alone."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops, harness, spec, weights

BENCH = spec.load_benchmark()
CONFIG = "k-exaone-236b-a23b-serve-1chip"
CELL = "kexaone.reason-offline"
SOURCE = ("https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/"
          "blob/main/config.json")
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "sliding_windows", "num_experts", "vocab_size"]


def _load(relative):
    return spec.load_module(spec.ROOT, BENCH, relative)


@pytest.fixture(scope="module")
def sized():
    config = spec.load_config(CONFIG)
    module = spec.load_model(config)
    return config, module, module.dims(config)


@pytest.fixture(scope="module")
def tiny():
    """The configuration at its rehearse_tiny size, float32 weights
    from a seed, the program's model and its parameters."""
    from batch_shipyard_tpu.models import transformer as tfm
    config = harness.merged(spec.load_config(CONFIG), True)
    module = spec.load_model(config)
    dims = module.dims(config)
    params = weights.make_params(module.param_leaves(dims), 3,
                                 jnp.float32)
    program = dataclasses.replace(
        module.program_model(config, dims, config["engine"]),
        dtype=jnp.float32, param_dtype=jnp.float32)
    return config, module, dims, params, tfm.TransformerLM(program)


# ------------------------------------------------ the file and the cut


@pytest.mark.parametrize("key, value", [
    ("hidden_size", 6144), ("num_attention_heads", 64),
    ("num_key_value_heads", 8), ("head_dim", 128),
    ("intermediate_size", 18432), ("moe_intermediate_size", 2048),
    ("num_experts_per_tok", 8), ("num_shared_experts", 1),
    ("sliding_window", 128), ("rms_norm_eps", 1e-5),
    ("routed_scaling_factor", 2.5), ("scoring_func", "sigmoid"),
    ("first_k_dense_replace", 1), ("num_nextn_predict_layers", 1),
    ("max_position_embeddings", 262144), ("n_group", 1),
    ("topk_group", 1), ("norm_topk_prob", True),
    ("tie_word_embeddings", False), ("model_type", "exaone_moe"),
    ("hidden_act", "silu"), ("sliding_window_pattern", "LLLG"),
    ("mtp_layer_types", ["full_attention"]),
    ("mtp_sliding_windows", [0]),
    ("rope_parameters", {"rope_theta": 1000000,
                         "rope_type": "default"})])
def test_the_file_states_the_published_value_uncut(sized, key, value):
    config, _module, _dims = sized
    assert config[key] == value
    assert key not in config["reduced"]


def test_the_cut_is_the_dense_layer_one_period_and_a_share(sized):
    config, module, dims = sized
    published = config["published"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == list(published) \
        == REDUCED
    assert entry["source"] == config["source"] == SOURCE
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"]) == (48, 128, 153600)
    assert published["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 12
    assert published["sliding_windows"] == [128, 128, 128, 0] * 12
    assert published["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert config["num_hidden_layers"] == 5
    for key in ("layer_types", "mlp_layer_types", "sliding_windows"):
        assert config[key] == published[key][:5]
    assert (config["num_experts"], config["vocab_size"]) == (16, 19200)
    assert config["share"]["chips_sharing_a_layer"] == 8
    assert 8 * 16 == config["share"]["experts_of"] == 128
    assert 8 * 19200 == config["share"]["vocab_rows_of"] == 153600
    assert dims["kinds"] == ("attn", "mlp") + ("attn", "experts") * 4
    assert dims["windows"] == (128, 128, 128, 0, 128)
    assert dims["ropes"] == (1, 1, 1, 0, 1)
    assert dims["dense"] == (1, 0, 0, 0, 0)
    assert dims["n_kind"] == {"attn_full": 1, "attn_window": 4,
                              "mlp": 1, "experts": 4}
    assert (dims["experts_held"], dims["n_router"], dims["top_k"],
            dims["first_expert"]) == (16, 128, 8, 0)
    assert [name for name, _k, _n in module.decision_layers(
        config, dims)] == ["layer_3", "layer_5", "layer_7", "layer_9",
                           "mtp"]
    assert {(k, n) for _name, k, n in module.decision_layers(
        config, dims)} == {(8, 128)}
    for key in ("assumed", "deployment", "sizing", "precision",
                "engine_built_through", "departures"):
        assert config[key]
    assumed = " ".join(config["assumed"])
    for said in ("PRE-norm", "EXAONE 4.0", "q/k norms",
                 "rotation on sliding layers only", "DeepSeek-V3",
                 "BEFORE the stack's final norm",
                 "e_score_correction_bias zeros", "qk_gain"):
        assert said in assumed, said
    departures = " ".join(config["departures"])
    assert "1 BLOCK IN 6 WHERE IT IS 1 IN 49" in departures
    assert "no statement about what self-drafting is worth" \
        in departures
    engine = config["engine"]
    assert (engine["num_slots"], engine["max_decode_len"],
            engine["kv_page_size"], engine["speculative"]) == (
                96, 8192, 64, False)


def test_every_stated_control_builds_the_program_it_names(sized):
    from benchmark import check
    config, module, dims = sized
    sound = module.program_model(config, dims, config["engine"])
    assert sound.attn_softmax_dtype == jnp.float32
    assert sound.experts.router_dtype == jnp.float32
    assert (sound.mtp_modules, sound.qk_norm, sound.mtp_rope) == (
        1, True, False)
    assert sound.layer_windows == (128, 0) * 3 + (0, 0, 128, 0)
    assert sound.layer_rope[::2] == (True, True, True, False, True)
    assert sound.experts.scoring == "sigmoid" and sound.experts.gated
    assert (sound.experts.scale, sound.experts.d_shared,
            sound.experts.held, sound.d_ff) == (2.5, 2048, 16, 18432)
    stated = check.controls(config["check"]["control"])
    assert stated == [{"decisions": {"reroute_share": 0.01}},
                      {"windows_off": [0]},
                      {"attn_softmax_dtype": "bfloat16"}]
    # the window off published layer 0 alone (what the chip has room
    # for beside the pool), or off every window layer
    assert module.program_model(
        config, dims, config["engine"],
        windows_off=[0]).layer_windows == (0, 0) + sound.layer_windows[2:]
    assert not any(module.program_model(
        config, dims, config["engine"], windows_off=True).layer_windows)
    for overrides in stated:
        overrides = {k: v for k, v in overrides.items()
                     if k != "decisions"}
        built = module.program_model(config, dims, config["engine"],
                                     **overrides)
        assert (built != sound) == bool(overrides)
    plain = module.program_model(config, dims, config["engine"],
                                 mtp_modules=0)
    assert plain.mtp_modules == 0


def test_the_arithmetic_is_the_issues(sized):
    _config, module, dims = sized
    d = 6144
    attn = d * (8192 + 1024 + 1024) + 8192 * d
    dense = 3 * d * 18432
    router, expert = d * 128, 3 * d * 2048
    sparse = router + expert + 16 * expert     # shared + 16 held
    norms = 2 * d + 2 * 128                    # two blocks, q and k
    layer0 = attn + dense + norms
    layer = attn + sparse + norms + 128        # the selection bias
    mtp = 2 * d * d + layer + 3 * d
    count = flops.param_count(module.param_leaves(dims))
    assert count == layer0 + 4 * layer + mtp + 2 * 19200 * d + d
    assert round(attn / 1e6, 2) == 113.25
    assert round(dense / 1e6, 2) == 339.74
    assert round(16 * expert / 1e6, 2) == 603.98
    assert round(layer0 / 1e6, 1) == 453.0
    assert round(layer / 1e6, 1) == 755.8
    assert round(mtp / 1e6, 1) == 831.3
    assert round(count / 1e9, 3) == 4.543           # 9.09 GB bfloat16
    assert dims["params"] == {
        "attn": attn, "mlp": dense,
        "experts_always": router + expert, "expert": expert,
        "head": d * 19200, "mtp_proj": 2 * d * d}
    # K and V of 8 heads of 128 in 2 bytes: 4 KiB a token a layer
    assert dims["kv_bytes_per_token_layer"] == 4096
    # a page of the pool serves 2 layers: 512 KiB
    assert 2 * 64 * 4096 == 512 * 1024


def test_the_traffic_is_the_issues_letter_for_letter():
    cell = spec.load_cell(CELL)
    traffic = cell.traffic
    assert {key: traffic[key] for key in (
        "kind", "path_seed", "clients", "client_stagger_s",
        "pool_requests", "lead_in_s", "shared_prefix_tokens",
        "trace_slice_s")} == {
            "kind": "serve-closed", "path_seed": 42, "clients": 96,
            "client_stagger_s": 0.05, "pool_requests": 1024,
            "lead_in_s": 15, "shared_prefix_tokens": 0,
            "trace_slice_s": 4}
    assert traffic["prompt_tokens"] == {
        "median": 512, "sigma": 0.7, "min": 128, "max": 2048}
    assert traffic["output_tokens"] == {
        "median": 1024, "sigma": 0.5, "min": 256, "max": 3072}
    assert traffic["clients"] == cell.config["engine"]["num_slots"]
    assert cell.chips == 1
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "setup_s"}
    # the longest request fits the engine's context with its draft
    assert 2048 + 3072 + 1 <= cell.config["engine"]["max_decode_len"]


NEW = ("mtp_accept_pct", "mtp_step_roofline", "decode_step_roofline",
       "gqa_paged_decode_roofline")
BY_READERS_THAT_WERE_THERE = (
    "decode_launch_p50_ms", "decode_step_p50_ms", "step_host_p50_ms",
    "host_behind_pct", "batch_occupancy_pct", "hbm_peak_pct",
    "kv_pages_peak_pct", "window_attended_pct", "window_pages_peak_pct",
    "prefill_device_share_pct", "prefill_padding_pct",
    "expert_rows_per_expert", "routed_here_pct", "idle_step_loop_pct",
    "idle_admit_pct")


def test_every_entry_it_brought_lists_its_cell_alone():
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.per_layer} == {
        f"{name}.kexaone" for name in NEW + BY_READERS_THAT_WERE_THERE}
    for metric in cell.per_layer:
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_tokens_per_s"
    for other in BENCH["workloads"]:
        if other["name"] != CELL:
            assert not any(
                m["name"].endswith(".kexaone")
                for m in spec.load_cell(other["name"]).per_layer)
    rooflines = [m for m in cell.per_layer if "roofline" in m["name"]]
    assert len(rooflines) == 3
    assert {m["unit"] for m in rooflines} == {"%"}


@pytest.mark.parametrize("name", NEW + BY_READERS_THAT_WERE_THERE)
def test_each_metric_has_its_definition_and_an_old_reader(name):
    definition = spec.layer_metric_file(f"{name}.kexaone")
    entry = next(m for m in BENCH["per_layer"]
                 if m["name"] == f"{name}.kexaone")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert definition[key] == entry[key]
    # one reader is new (the module's span: below); every other is
    # one an accepted metric already uses
    readers = {spec.layer_metric_file(m["name"])["reader"]
               for m in BENCH["per_layer"]
               if not m["name"].endswith(".kexaone")}
    if name == "mtp_step_roofline":
        assert definition["reader"] == "span_in_program_roofline"
    else:
        assert definition["reader"] in readers
    if name in BY_READERS_THAT_WERE_THERE:
        sibling = next(
            spec.layer_metric_file(f"{name}.{suffix}")
            for suffix in ("smallthinker", "solaropen2")
            if (spec.ROOT / "benchmark" / "layer_metrics"
                / f"{name}.{suffix}.json").is_file())
        assert (definition["reader"], definition["params"]) == (
            sibling["reader"], sibling["params"])


def test_the_modules_device_events_are_found_by_its_operands():
    import re
    definition = spec.layer_metric_file("mtp_step_roofline.kexaone")
    assert definition["params"]["kernel"] == "mtp_step"
    pattern = re.compile(definition["params"]["event_pattern"])
    assert pattern.search(
        "%fusion.7 = bf16[192,128]{1,0} fusion(bf16[192,6144]{1,0} "
        "%x, bf16[6144,128]{1,0} "
        "%params__mtp____layer_1____experts____router_kernel__.1)")
    assert pattern.search(
        "%gqa_paged_decode.6 = bf16[96,128,128]{2,1,0} custom-call("
        "..., %cache__mtp____layer_0____attn____k_pages__.1, ...)")
    assert not pattern.search(
        "%fusion.9 = ... %params__layer_9____experts____experts_up__")
    assert not pattern.search("%fusion.2 = ... %params__lm_head____"
                              "kernel__.1")
    from batch_shipyard_tpu.ops import paged_attention as pa
    kernel = re.compile(spec.layer_metric_file(
        "gqa_paged_decode_roofline.kexaone")["params"][
            "event_patterns"]["decode"])
    assert kernel.search(f"%{pa.GQA_KERNEL_NAME}.3 = bf16[96,128,128]"
                         f"{{2,1,0}} custom-call(...)")
    program = re.compile(spec.layer_metric_file(
        "decode_step_roofline.kexaone")["params"]["program_pattern"])
    assert program.search("jit__decode_step(1234)")


def _module_trace():
    """Two launches of the decode step and one of a prefill on one
    device; in each, operations that name the module's arguments, one
    of the module's that names none (a matmul fed by a prefetched
    slice), the head behind them, a stack operation before them."""
    stack = ("%fusion.9 = bf16[192,16,2048] fusion(%x, "
             "%params__layer_9____experts____experts_up__.1)")
    issue = ("%slice-start.66 = (...) slice-start(%params__mtp____"
             "layer_0____attn____v_proj____kernel__.1), slice={...}")
    proj = ("%fusion.149 = (...) fusion(%params__mtp____proj____"
            "kernel__.1, %fusion.114, %fusion.148)")
    unnamed = "%fusion.150 = bf16[192,8192] fusion(%x, %custom-call.57)"
    pages = ("%fusion.21 = bf16[4609,64,1024] fusion(%cache__mtp____"
             "layer_0____attn____k_pages__.1, %reshape.453)")
    rewind = ("%copy-start.61 = (...) copy-start(%cache__mtp____"
              "layer_0____attn____length__.1)")
    head = ("%iota_reduce_fusion = (...) fusion(%params__lm_head____"
            "kernel__.1, %copy-done.48)")
    loop = ("%while.3 = () while((s32[]) %cache__mtp____layer_0____"
            "attn____length__.1)")

    def launch(at):
        return [[stack, at + 100, 900], [issue, at + 1000, 5],
                [proj, at + 1100, 300], [unnamed, at + 1400, 700],
                [pages, at + 2100, 100], [rewind, at + 2900, 10],
                [head, at + 3000, 400], [loop, at + 50, 3900]]

    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit__decode_step(1)", 0, 4000],
                ["jit__decode_step(1)", 10000, 4000],
                ["jit__prefill_paged(2)", 20000, 4000]]},
            {"name": "XLA Ops", "events":
                launch(0) + launch(10000) + launch(20000)}]},
        {"name": "/host:CPU", "lines": []}]}


def test_the_module_is_timed_by_the_span_of_its_named_operations(
        sized, tmp_path):
    _config, _module, dims = sized
    definition = spec.layer_metric_file("mtp_step_roofline.kexaone")
    params = definition["params"]
    reader = _load("layer_metrics/readers/span_in_program_roofline.py")
    trace = _module_trace()
    # from the prefetch's issue at 1000 to the rewind's end at 2910, in
    # each of the two decode launches: the unnamed matmul between them
    # is inside, the stack before, the head behind and the container
    # are not
    assert reader.span_seconds(
        trace, params["program_pattern"], params["event_pattern"]) == (
            pytest.approx(2 * 1910e-9), 2)
    assert reader.span_seconds(trace, "_prefill_paged",
                               params["event_pattern"]) == (
        pytest.approx(1910e-9), 1)
    assert reader.span_seconds(trace, "nothing",
                               params["event_pattern"]) == (0.0, 0)
    assert reader.span_seconds(trace, params["program_pattern"],
                               "params__nothing") == (0.0, 0)
    # the named operations alone: what ops_in_program_roofline sums,
    # short of the span by the unnamed matmul and the gaps
    named = _load("layer_metrics/readers/ops_in_program_roofline.py")
    assert named.seconds_inside(
        trace, params["program_pattern"], params["event_pattern"]) == (
            pytest.approx(2 * 415e-9), 2)
    _rows(tmp_path, ROWS)
    obs = {"profile": {"trace": trace, "started": 100.0,
                       "stopped": 110.0},
           "peaks": {"bf16_flops_per_s": 197e12,
                     "hbm_bytes_per_s": 819e9},
           "dims": dims, "out_dir": tmp_path}
    one = _load("kernels/mtp_step.py").step_work(
        dims, slots=95, hit=79, pairs=950, full=115000, window=12160)
    assert reader.read(obs, params) == pytest.approx(
        100.0 * 2 * one["bytes"] / 819e9 / (2 * 1910e-9))
    assert obs["roofline_bound"] == {"mtp_step": "memory"}
    # nothing to read: no profile, no trace, rows without the counters
    assert reader.read({"profile": None, "peaks": obs["peaks"]},
                       params) is None
    assert reader.read({}, params) is None
    _rows(tmp_path, [{"slots_active": 3}])
    assert reader.read({key: value for key, value in obs.items()
                        if key in ("profile", "peaks", "dims",
                                   "out_dir")}, params) is None


# ------------------------------------------------- the work functions


def test_verify_paged_decode_work_by_hand():
    work = _load("kernels/paged_decode_verify.py")
    one = work.call_work(tokens=100000, slots=96, drafts=1, n_heads=64,
                         n_kv_heads=8, d_head=128)
    # K and V rows of 8 x 128 lanes in 2 bytes a key, each slot's one
    # key more for the second position, read once for both; queries in
    # and outputs out at two positions
    assert one["bytes"] == 2 * (100000 + 96) * 1024 * 2 \
        + 2 * 2 * 96 * 8192 * 2
    assert one["flops"] == 4 * (100000 + 96) * 8192 * 2
    # at no draft it is the windowed kernel's count
    plain = _load("kernels/paged_decode_windowed.py").call_work(
        100000, 96, 64, 8, 128)
    assert work.call_work(100000, 96, 0, 64, 8, 128) == plain


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


ROWS = [{"slots_active": 96, "kv_tokens_full": 120000,
         "kv_tokens_window": 12288, "expert_pairs_chosen": 7680,
         "expert_pairs_here": 960, "experts_hit": 80,
         "mtp_drafted": 96, "mtp_accepted": 0,
         "window_pages_in_use": 288, "window_pages_total": 384},
        {"slots_active": 94, "kv_tokens_full": 110000,
         "kv_tokens_window": 12032, "expert_pairs_chosen": 7520,
         "expert_pairs_here": 940, "experts_hit": 78,
         "mtp_drafted": 94, "mtp_accepted": 2,
         "window_pages_in_use": 282, "window_pages_total": 384},
        # a call that landed no decode step
        {"slots_active": 95, "kv_tokens_full": 115000,
         "kv_tokens_window": 12160, "expert_pairs_chosen": 0,
         "expert_pairs_here": 0, "experts_hit": 0, "mtp_drafted": 0,
         "mtp_accepted": 0, "window_pages_in_use": 285,
         "window_pages_total": 384},
        # outside the window
        {"mono_start": 99.0, "slots_active": 96, "kv_tokens_full": 1,
         "kv_tokens_window": 1, "expert_pairs_chosen": 7680,
         "expert_pairs_here": 7680, "experts_hit": 80,
         "mtp_drafted": 96, "mtp_accepted": 96,
         "window_pages_in_use": 384, "window_pages_total": 384}]


def test_the_work_over_a_traced_slice_by_hand(sized, tmp_path):
    _config, _module, dims = sized
    _rows(tmp_path, ROWS)
    obs = {"profile": {"started": 100.0, "stopped": 110.0},
           "dims": dims, "out_dir": tmp_path}
    p = dims["params"]
    # the mean of the two rows that landed a step
    slots, hit, pairs, full, window = 95, 79, 950, 115000, 12160
    kernel = _load("kernels/paged_decode_verify.py")
    # 12 kernel calls = 2 steps of 6 attention blocks: 2 full (the
    # stack's and the module's), 4 window
    got = kernel.work(obs, {"decode": 12})
    one_full = kernel.call_work(full, slots, 1, 64, 8, 128)
    one_window = kernel.call_work(window, slots, 1, 64, 8, 128)
    assert got["bytes"] == pytest.approx(
        2 * (2 * one_full["bytes"] + 4 * one_window["bytes"]))
    assert got["flops"] == pytest.approx(
        2 * (2 * one_full["flops"] + 4 * one_window["flops"]))
    step = _load("kernels/verify_step.py")
    one = step.step_work(dims, slots=slots, hit=hit, pairs=pairs,
                         full=full, window=window)
    assert step.work(obs, {"program": 3}) == pytest.approx(
        {name: 3 * value for name, value in one.items()})
    always = (2 * p["head"] + 6 * p["attn"] + p["mlp"]
              + 5 * p["experts_always"] + p["mtp_proj"])
    keys = 2 * (full + slots) + 4 * (window + slots)
    assert one["bytes"] == pytest.approx(
        2 * (always + hit * p["expert"] + 2 * 2 * 6144 * slots)
        + 4096 * keys)
    assert one["flops"] == pytest.approx(
        2 * (always * 2 * slots + p["expert"] * pairs)
        + 4 * 8192 * keys * 2)
    # the issue's reckoning of a full step's weights: everything held
    # once and the head a second time, 9.09 GB + 0.24 - the embedding
    weights = 2 * (always + 80 * p["expert"])
    assert round(weights / 1e9, 2) == round(
        (2 * 4.5432e9 + 2 * p["head"] - 2 * 19200 * 6144) / 1e9, 2)
    module = _load("kernels/mtp_step.py")
    part = module.step_work(dims, slots=slots, hit=hit, pairs=pairs,
                            full=full, window=window)
    assert module.work(obs, {"program": 3}) == pytest.approx(
        {name: 3 * value for name, value in part.items()})
    own = p["mtp_proj"] + p["attn"] + p["experts_always"]
    assert part["bytes"] == pytest.approx(
        2 * (own + hit / 5 * p["expert"]) + 4096 * (full + slots))
    assert part["flops"] == pytest.approx(
        2 * (own * 2 * slots + p["expert"] * pairs / 5)
        + 4 * 8192 * (full + slots) * 2)
    # the module is a part of the step
    assert part["bytes"] < one["bytes"] / 4


def test_the_acceptance_counter_by_hand(tmp_path):
    _rows(tmp_path, ROWS)
    reader = _load("layer_metrics/readers/rows_ratio.py")
    params = spec.layer_metric_file("mtp_accept_pct.kexaone")["params"]
    assert reader.read({"out_dir": tmp_path}, params) == pytest.approx(
        100 * 2 / 190)


def test_the_new_metrics_read_none_without_the_programs_counters(
        sized, tmp_path):
    """The parent commit cannot run the configuration at all; a program
    that writes rows without the drafter's attrs, and a run without
    rows, leave each metric out, and nothing raises."""
    _config, _module, dims = sized
    reader = _load("layer_metrics/readers/rows_ratio.py")
    params = spec.layer_metric_file("mtp_accept_pct.kexaone")["params"]
    assert reader.read({"out_dir": tmp_path}, params) is None
    assert reader.read({}, params) is None
    _rows(tmp_path, [{"slots_active": 3, "expert_pairs_chosen": 12,
                      "expert_pairs_here": 12, "experts_hit": 9,
                      "kv_tokens_full": 10, "kv_tokens_window": 10}])
    assert reader.read({"out_dir": tmp_path}, params) is None
    obs = {"profile": {"started": 100.0, "stopped": 110.0},
           "dims": dims, "out_dir": tmp_path}
    for kernel in ("verify_step", "mtp_step"):
        assert _load(f"kernels/{kernel}.py").work(
            obs, {"program": 8}) is None
    assert _load("kernels/paged_decode_verify.py").work(
        {"dims": dims}, {"decode": 8}) is None


# ------------------------------------- the tree, and the mathematics


def test_the_tree_is_the_programs_tree(tiny):
    _config, module, dims, params, model = tiny
    made = model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 4), jnp.int32))["params"]
    want = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), made)
    got = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), params)
    assert got == want
    assert set(params["mtp"]) == {"embed_norm", "hidden_norm", "proj",
                                  "layer_0", "layer_1", "norm"}
    assert set(params["layer_0"]["attn"]) == {
        "q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm"}
    assert set(params["layer_1"]) == {"norm", "mlp"}


def _program_logits(model, params, tokens):
    """(stack logits [T, V], module logits [T, V]) of one full forward
    of the program without a cache."""
    (logits, hidden), _ = model.apply(
        {"params": params}, tokens[None], stack_hidden=True,
        mutable=["decisions"])
    following = jnp.concatenate([tokens[1:], tokens[:1] * 0])
    module, _ = model.apply(
        {"params": params}, following[None], mtp_hidden=hidden,
        mutable=["decisions"])
    return logits[0], module[0]


def test_stack_and_module_logits_match_the_reference(tiny):
    config, module, dims, params, model = tiny
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(1, dims["vocab"], 90), jnp.int32)
    rows = jnp.arange(90)
    want, want_module = module.teacher_forced_logits(
        params, tokens, rows, config, dims, mtp_rows=rows[:-1])
    got, got_module = _program_logits(model, params, tokens)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(got_module[:-1], want_module,
                               atol=2e-4, rtol=2e-4)
    # a window of 32 under 90 tokens: the band matters
    assert dims["window"] == 32 < 90


@pytest.mark.parametrize("what", ["dense_layer", "qk_norms"])
def test_the_dense_layer_and_the_norms_against_the_reference(tiny,
                                                             what):
    """Each taken alone: the leading dense layer's feed-forward and the
    q/k norms move the reference's logits, and the program follows."""
    config, module, dims, params, model = tiny
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(1, dims["vocab"], 40), jnp.int32)
    rows = jnp.arange(40)
    changed = jax.tree_util.tree_map(lambda x: x, params)
    if what == "dense_layer":
        changed["layer_1"]["mlp"]["down_proj"]["kernel"] = \
            3.0 * params["layer_1"]["mlp"]["down_proj"]["kernel"]
    else:
        scale = jnp.linspace(0.5, 2.0, dims["d_head"])
        for i in range(0, dims["n_layers"], 2):
            changed[f"layer_{i}"]["attn"]["q_norm"]["scale"] = scale
            changed[f"layer_{i}"]["attn"]["k_norm"]["scale"] = \
                scale[::-1]
    before = module.teacher_forced_logits(params, tokens, rows, config,
                                          dims)
    want = module.teacher_forced_logits(changed, tokens, rows, config,
                                        dims)
    assert float(jnp.max(jnp.abs(want - before))) > 1e-2
    got, _module = _program_logits(model, changed, tokens)
    np.testing.assert_allclose(got, want, atol=3e-4, rtol=3e-4)


def test_the_chips_parts_of_a_sparse_layer_add_up(tiny):
    """The share: each of the chips that share a layer computes the
    routed sum over ITS experts; the parts, the shared expert counted
    once, add up to the uncut layer (all experts held)."""
    from benchmark.reference import kexaone_plain as plain
    _config, _module, dims, _params, _model = tiny
    d, f, n, k = dims["d_model"], dims["d_expert"], dims["n_router"], \
        dims["top_k"]
    held = dims["experts_held"]
    chips = n // held
    assert chips == 2
    key = jax.random.split(jax.random.PRNGKey(5), 8)
    whole = {
        "router_kernel": jax.random.normal(key[0], (d, n)) / d ** 0.5,
        "e_score_correction_bias": jnp.zeros((n,)),
        "experts_gate": jax.random.normal(key[1], (n, d, f)) / d ** 0.5,
        "experts_up": jax.random.normal(key[2], (n, d, f)) / d ** 0.5,
        "experts_down": jax.random.normal(key[3], (n, f, d)) / f ** 0.5,
        "shared_gate": jax.random.normal(key[4], (d, f)) / d ** 0.5,
        "shared_up": jax.random.normal(key[5], (d, f)) / d ** 0.5,
        "shared_down": jax.random.normal(key[6], (f, d)) / f ** 0.5}
    m = jax.random.normal(key[7], (24, d))
    own = jnp.full((24, k), -1, jnp.int32)
    sizes = dict(top_k=k, scale=dims["scale"])
    uncut, _ = plain.experts(m, whole, own, first=0, **sizes)
    shared = plain.swiglu(m, whole["shared_gate"], whole["shared_up"],
                          whole["shared_down"])
    parts = []
    for chip in range(chips):
        part = dict(whole)
        for name in ("experts_gate", "experts_up", "experts_down"):
            part[name] = whole[name][chip * held:(chip + 1) * held]
        out, _ = plain.experts(m, part, own, first=chip * held, **sizes)
        parts.append(out - shared)
    np.testing.assert_allclose(sum(parts) + shared, uncut, atol=1e-4,
                               rtol=1e-4)
    # and a part alone is not the whole
    assert float(jnp.max(jnp.abs(parts[0] + shared - uncut))) > 1e-2

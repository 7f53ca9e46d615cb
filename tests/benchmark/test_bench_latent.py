"""What the configuration with latent attention under sandwich norms,
256 sigmoid-routed experts and a multi-token-prediction module adds to
the benchmark beside its model module and reference (those are tested,
as every configuration's, by test_bench_reference and
test_bench_rehearsal, and through the engine by
tests/test_latent_serving.py): its file's published widths, cut and
share, the parameter arithmetic, the traffic file letter for letter,
the two work functions and the new reader by hand, and that every
entry it brought lists its one cell alone."""

import dataclasses
import json

import pytest

from benchmark import spec

BENCH = spec.load_benchmark()
CONFIG = "openpangu-ultra-moe-718b-serve-1chip"
CELL = "openpangu.longreason-offline"
SOURCE = ("https://huggingface.co/FreedomIntelligence/"
          "openPangu-Ultra-MoE-718B/blob/main/config.json")
REDUCED = ["num_hidden_layers", "first_k_dense_replace",
           "n_routed_experts", "vocab_size"]
NEW = ("mla_paged_decode_roofline", "latent_decode_share_pct",
       "decode_step_roofline", "mtp_accept_pct", "decode_launch_p50_ms",
       "decode_step_p50_ms", "step_host_p50_ms", "host_behind_pct",
       "batch_occupancy_pct", "hbm_peak_pct", "kv_pages_peak_pct",
       "prefill_device_share_pct", "prefill_ms_per_ktoken",
       "prefill_padding_pct", "expert_rows_per_expert",
       "routed_here_pct", "idle_step_loop_pct", "idle_admit_pct",
       "latent_prefill_roofline")


def _load(relative):
    return spec.load_module(spec.ROOT, BENCH, relative)


@pytest.fixture(scope="module")
def sized():
    config = spec.load_config(CONFIG)
    module = spec.load_model(config)
    return config, module, module.dims(config)


@pytest.mark.parametrize("key, value", [
    ("hidden_size", 7680), ("num_attention_heads", 128),
    ("num_key_value_heads", 128), ("q_lora_rank", 1536),
    ("kv_lora_rank", 512), ("qk_nope_head_dim", 128),
    ("qk_rope_head_dim", 64), ("v_head_dim", 128),
    ("intermediate_size", 18432), ("moe_intermediate_size", 2048),
    ("num_experts_per_tok", 8), ("n_shared_experts", 1),
    ("routed_scaling_factor", 2.5), ("norm_topk_prob", True),
    ("sandwich_norm", True), ("rms_norm_eps", 1e-5),
    ("rope_theta", 25600000), ("num_nextn_predict_layers", 1),
    ("max_position_embeddings", 131072), ("attention_bias", False),
    ("tie_word_embeddings", False), ("hidden_act", "silu"),
    ("model_type", "pangu_ultra_moe")])
def test_the_file_states_the_published_value_uncut(sized, key, value):
    config, _module, _dims = sized
    assert config[key] == value
    assert key not in config["reduced"]


def test_the_cut_is_a_dense_layer_four_sparse_ones_and_a_share(sized):
    config, _module, dims = sized
    published = config["published"]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"] == list(published) \
        == REDUCED
    assert entry["source"] == config["source"] == SOURCE
    assert published == {"num_hidden_layers": 61,
                         "first_k_dense_replace": 3,
                         "n_routed_experts": 256, "vocab_size": 153600}
    assert (config["num_hidden_layers"],
            config["first_k_dense_replace"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 1, 8, 19200)
    share = config["share"]
    assert share["chips_sharing_a_layer"] == 32
    assert 32 * 8 == share["experts_of"] == 256
    assert 8 * 19200 == share["vocab_rows_of"] == 153600
    assert (share["first_expert"], share["first_vocab_row"]) == (0, 0)
    assert dims["kinds"] == ("attn", "mlp") + ("attn", "experts") * 4
    assert dims["dense"] == (1, 0, 0, 0, 0)
    assert dims["n_kind"] == {"attn_full": 5, "attn_window": 0,
                              "mlp": 1, "experts": 4}
    assert (dims["experts_held"], dims["n_router"], dims["top_k"],
            dims["mtp_modules"], dims["drafts"]) == (8, 256, 8, 1, 1)
    # the guide's floors: four sparse layers behind the dense ones, 8
    # routed experts a layer, an eighth of the vocabulary
    assert dims["n_kind"]["experts"] >= 4 and dims["experts_held"] >= 8
    assert 8 * dims["vocab"] >= published["vocab_size"]
    assert spec.decision_layers(spec.load_model(config), config,
                                dims) == [
        (name, 8, 256) for name in
        ("layer_3", "layer_5", "layer_7", "layer_9", "mtp")]
    engine = config["engine"]
    assert (engine["num_slots"], engine["max_decode_len"],
            engine["kv_page_size"]) == (128, 12288, 64)
    assert engine["overcommit"] is False and engine["prefix_cache"]
    assert [list(c) for c in config["check"]["control"]] == [
        ["decisions"], ["decode_rope"], ["attn_softmax_dtype"]]


def test_every_stated_control_builds_the_program_it_names(sized):
    import jax.numpy as jnp
    config, module, dims = sized
    sound = module.program_model(config, dims, config["engine"])
    from batch_shipyard_tpu.models import transformer as tfm
    assert type(sound.latent) is tfm.LatentKV and sound.sandwich_norm
    assert (sound.latent.q_rank, sound.latent.kv_rank,
            sound.latent.nope_dim, sound.latent.rope_dim,
            sound.latent.v_dim, sound.latent.row_lanes) == (
                1536, 512, 128, 64, 128, 640)
    assert sound.mtp_modules == 1 and sound.prefill_blocks
    assert sound.attn_softmax_dtype == jnp.float32
    assert (sound.experts.n_experts, sound.experts.top_k,
            sound.experts.experts_held, sound.experts.scale) == (
                256, 8, 8, 2.5)
    _reroute, no_rope, rough = config["check"]["control"]
    faulty = module.program_model(
        config, dims, config["engine"], **no_rope).latent
    # the fault is the module's own construction over the served
    # class, which has no field for it
    assert type(faulty) is tfm.LatentAttention.rope_left_out
    assert dataclasses.asdict(faulty) == dataclasses.asdict(
        sound.latent)
    assert sound.experts.router_dtype == jnp.float32
    assert module.program_model(
        config, dims, config["engine"],
        **rough).attn_softmax_dtype == jnp.bfloat16


def test_the_arithmetic_is_the_issues(sized):
    """Parameters from the tree itself: 196.6 M an attention block,
    424.7 M the dense layer, 47.19 M an expert, 4,150 M in all
    (8.30 GB in bfloat16); a cached row 576 lanes of numbers in 640."""
    import math
    _config, module, dims = sized
    leaves = module.param_leaves(dims)
    total = sum(math.prod(shape) for _path, shape, *_ in leaves)
    assert round(total / 1e6) == 4150
    params = dims["params"]
    assert params["attn"] == (
        7680 * 1536 + 1536 * 24576 + 7680 * 576 + 512 * 32768
        + 16384 * 7680) == 196575232
    assert params["mlp"] == 3 * 7680 * 18432
    assert params["expert"] == 3 * 7680 * 2048
    assert params["experts_always"] == 7680 * 256 + params["expert"]
    assert params["head"] == 7680 * 19200
    assert params["mtp_proj"] == 15360 * 7680
    assert (dims["row_lanes"], dims["row_lanes_stored"],
            dims["kv_bytes_per_token_layer"]) == (576, 640, 1152)
    by_block = {}
    for path, shape, *_ in leaves:
        by_block[path[0]] = by_block.get(path[0], 0) + math.prod(shape)
    assert by_block["layer_0"] == params["attn"] + 2 * 7680 + 1536 + 512
    assert by_block["mtp"] == (
        params["mtp_proj"] + params["attn"] + params["experts_always"]
        + 8 * params["expert"] + 256 + 7 * 7680 + 1536 + 512)


def test_the_traffic_is_the_issues_letter_for_letter():
    cell = spec.load_cell(CELL)
    traffic = cell.traffic
    assert cell.chips == 1 and cell.config_name == CONFIG
    assert traffic["kind"] == "serve-closed"
    assert (traffic["clients"], traffic["client_stagger_s"],
            traffic["pool_requests"], traffic["shared_prefix_tokens"],
            traffic["trace_slice_s"]) == (128, 0.05, 1024, 0, 4)
    assert traffic["clients"] == cell.config["engine"]["num_slots"]
    assert traffic["prompt_tokens"] == {
        "median": 2048, "sigma": 0.7, "min": 512, "max": 8192}
    assert traffic["output_tokens"] == {
        "median": 1536, "sigma": 0.5, "min": 512, "max": 4096}
    assert "eos" not in json.dumps(traffic).lower()
    # a request's most fits a slot
    assert 8192 + 4096 <= cell.config["engine"]["max_decode_len"]
    assert "rehearse_tiny" in traffic
    assert traffic["path_seed"] not in {
        json.load(open(path))["path_seed"] for path in
        (spec.ROOT / "benchmark" / "traffic").glob("*.json")
        if path.stem != cell.traffic_name}


def test_every_entry_it_brought_lists_its_cell_alone():
    brought = [m for m in BENCH["per_layer"]
               if m["name"].endswith(".openpangu")]
    assert [m["name"] for m in brought] == [
        f"{name}.openpangu" for name in NEW]
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "serve_tokens_per_s" for m in brought)
    e2e = next(m for m in BENCH["end_to_end"]
               if m["name"] == "serve_tokens_per_s")
    assert e2e["workloads"][-1] == CELL
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in brought}
    assert len(BENCH["workloads"]) == 8 and not any(
        w["chips"] != 1 for w in BENCH["workloads"])
    for metric in brought:
        stated = spec.layer_metric_file(metric["name"])
        assert {key: stated[key] for key in
                ("name", "unit", "better", "source", "layer",
                 "moves")} == {key: metric[key] for key in
                               ("name", "unit", "better", "source",
                                "layer", "moves")}
        _load(f"layer_metrics/readers/{stated['reader']}.py")


def test_latent_paged_decode_work_by_hand():
    work = _load("kernels/paged_decode_latent.py").call_work(
        tokens=400000.0, slots=128.0, drafts=1, n_heads=128,
        row_lanes=576, kv_rank=512)
    keys = 400000 + 128
    assert work["flops"] == 2.0 * keys * 128 * (576 + 512) * 2
    assert work["bytes"] == 2.0 * keys * 576 \
        + 2.0 * 2 * 128 * 128 * (576 + 512)
    # compute-bound at two positions: twice the v5e's ridge
    assert 400 < work["flops"] / work["bytes"] < 500


def test_latent_prefill_work_by_hand(tmp_path):
    module = _load("kernels/flash_prefill_latent.py")
    one = module.prefill_work(2000.0, 6, 128, 128, 64, 128)
    assert one["flops"] == 6 * 2.0 * 128 * 320 * 2000 * 2001 / 2
    assert one["bytes"] == 6 * 2.0 * 2000 * 128 * (2 * 192 + 2 * 128)
    # the prefills whose launch lies inside the slice, and no other
    landed = [
        {"kind": "prefill", "tokens": 1000, "bucket": 1024,
         "landed_at": 10.5, "period_ms": 100.0},
        {"kind": "prefill", "tokens": 3000, "bucket": 4096,
         "landed_at": 10.05, "period_ms": 100.0},     # began before it
        {"kind": "decode", "rows": 128, "landed_at": 10.6,
         "period_ms": 26.0},
        {"kind": "prefill", "tokens": 2000, "bucket": 2048,
         "landed_at": 12.0, "period_ms": 200.0}]
    obs = {"profile": {"started": 10.0, "stopped": 14.0},
           "out_dir": str(tmp_path), "step_rows": ([{"landed": landed}],
                                                    51.0),
           "dims": {"n_kind": {"attn_full": 5}, "mtp_modules": 1,
                    "n_heads": 128, "nope": 128, "rope": 64,
                    "v_dim": 128}}
    assert [x["tokens"] for x in module.slice_prefills(obs)] == [
        1000, 2000]
    total = module.work(obs, {"prefill": 48})
    assert total["flops"] == sum(
        module.prefill_work(n, 6, 128, 128, 64, 128)["flops"]
        for n in (1000.0, 2000.0))
    assert module.work(obs, {"prefill": 0}) is None
    assert module.work({"profile": None, "out_dir": None}, {"p": 1}) \
        is None


ROWS = [{"slots_active": 128, "kv_tokens_full": 400000,
         "experts_hit": 40, "expert_pairs_here": 320,
         "expert_pairs_chosen": 10240, "mtp_drafted": 128,
         "mtp_accepted": 1, "experts_held": 40}]


def test_the_step_work_by_hand(sized):
    _config, _module, dims = sized
    step = _load("kernels/verify_step_latent.py").step_work(
        dims, slots=128.0, hit=40.0, pairs=320.0, full=400000.0)
    params = dims["params"]
    always = (2 * params["head"] + 6 * params["attn"] + params["mlp"]
              + 5 * params["experts_always"] + params["mtp_proj"])
    keys = 6 * (400000 + 128)
    assert step["bytes"] == 2.0 * (
        always + 40 * params["expert"] + 2 * 2 * 7680 * 128) \
        + 2.0 * 576 * keys
    assert step["flops"] == 2.0 * (
        always * 2 * 128 + params["expert"] * 320) \
        + 2.0 * 128 * (576 + 512) * keys * 2
    # 8.3 GB of weights and 2.8 GB of rows: the issue's step
    assert 8.2e9 < 2.0 * (always + 40 * params["expert"]) < 8.4e9


def test_the_share_reader_by_hand():
    read = _load("layer_metrics/readers/kernel_share_of_program.py").read
    params = {"event_pattern": "mla_paged_decode[^=]* = .*custom-call",
              "program_pattern": "_decode_step"}
    assert read({}, params) is None
    assert read({"profile": {"events": {}, "trace": None}}, params) \
        is None
    kernel = "%mla_paged_decode.3 = bf16[128,256,512] custom-call(...)"
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit__decode_step(1)", 0, 20_000_000],
            ["jit__decode_step(1)", 30_000_000, 20_000_000],
            ["jit__prefill_paged(2)", 60_000_000, 90_000_000]]},
        {"name": "XLA Ops", "events": []}]}]}
    events = {0: [[kernel, 1_000_000, 3_000_000],
                  [kernel, 5_000_000, 3_000_000],
                  [kernel, 31_000_000, 4_000_000],
                  ["%fusion.7 = bf16[128,7680] fusion(...)", 0, 900]]}
    got = read({"profile": {"events": events, "trace": trace}}, params)
    assert got == pytest.approx(100.0 * 10 / 40)
    # a program without the kernel (the parent commit): nothing to read
    assert read({"profile": {"events": {0: events[0][3:]},
                             "trace": trace}}, params) is None

"""What the configuration that generates by diffusion over blocks adds
to the benchmark beside its model module and reference (those are
tested, as every configuration's, by test_bench_flops_and_kernels,
test_bench_reference and test_bench_rehearsal; the engine against the
reference on logits by tests/test_block_serving.py): its file's
published widths, cut and assumed sizes, the parameter arithmetic, the
traffic file against the one whose requests it serves, the two work
functions and the two new readers by hand, the reference's one forward
against a naive pass-by-pass loop, the check of its own driver (sound,
and each control over its limit) at the rehearsal's size, and that
every entry it brought lists its one cell alone."""

import dataclasses
import json
import types

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, flops, harness, spec, weights
from benchmark.drivers import serve
from benchmark.reference import sdar_plain as plain

BENCH = spec.load_benchmark()
CONFIG = "sdar-30b-a3b-chat-serve-1chip"
CELL = "sdar.blockgen-offline"
SOURCE = ("https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
          "config.json")


def _load(relative):
    return spec.load_module(spec.ROOT, BENCH, relative)


@pytest.fixture(scope="module")
def sized():
    config = spec.load_config(CONFIG)
    module = spec.load_model(config)
    return config, module, module.dims(config)


# ------------------------------------------------ the file and the cut


@pytest.mark.parametrize("key, value", [
    ("attention_bias", False), ("decoder_sparse_step", 1),
    ("head_dim", 128), ("hidden_act", "silu"), ("hidden_size", 2048),
    ("intermediate_size", 6144), ("max_position_embeddings", 32768),
    ("max_window_layers", 48), ("mlp_only_layers", []),
    ("model_type", "sdar_moe"), ("moe_intermediate_size", 768),
    ("norm_topk_prob", True), ("num_attention_heads", 32),
    ("num_experts", 128), ("num_experts_per_tok", 8),
    ("num_key_value_heads", 4), ("rms_norm_eps", 1e-6),
    ("rope_scaling", None), ("rope_theta", 1000000),
    ("sliding_window", None), ("tie_word_embeddings", False),
    ("use_sliding_window", False), ("vocab_size", 151936)])
def test_the_file_states_the_published_value_uncut(sized, key, value):
    config, _module, _dims = sized
    assert config[key] == value


def test_the_cut_and_the_assumed_sizes_are_written_down(sized):
    config, _module, dims = sized
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] == SOURCE
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["num_hidden_layers"] == 6
    assert config["generation"] == {
        "block_length": 4, "denoising_steps": 4,
        "remasking": "low_confidence_dynamic",
        "confidence_threshold": 0.9, "mask_token_id": 151669}
    assumed = " ".join(config["assumed"])
    for what in ("block length 4", "denoising steps 4",
                 "low_confidence_dynamic", "0.9", "151,669",
                 "q/k norms", "no logit shift", "lowest index"):
        assert what in assumed, what
    assert config["engine"] == {
        "num_slots": 96, "max_decode_len": 8192, "kv_page_size": 64,
        "kv_num_pages": 4608, "overcommit": False, "prefix_cache": True,
        "sampling": "greedy", "speculative": False}
    assert dims["experts_held"] == dims["n_router"] == 128
    assert dims["kinds"] == ("attn", "experts") * 6
    limits = config["check"]["limits"]
    assert set(limits) == {"gap_tail_mean", "routing_rejected_share",
                           "routing_slack_tail_mean",
                           "unmask_rejected_share"}
    assert len(check.controls(config["check"]["control"])) == 4


def test_the_issues_arithmetic(sized):
    _config, module, dims = sized
    params = dims["params"]
    assert params["attn"] == 2048 * (4096 + 512 + 512) + 4096 * 2048
    assert params["expert"] == 3 * 2048 * 768
    assert params["head"] == 151936 * 2048
    layer = (params["attn"] + params["experts_always"]
             + 128 * params["expert"] + 2 * 2048 + 2 * 128)
    count = flops.param_count(module.param_leaves(dims))
    assert count == 6 * layer + 2 * params["head"] + 2048
    assert round(2 * count / 1e9, 2) == 8.72       # bfloat16, GB
    assert round(layer / 1e6, 1) == 623.1
    assert dims["kv_bytes_per_token_layer"] == 2048
    # 96 slots x 4 positions x top-8 over 128 experts
    assert 96 * dims["block"] * dims["top_k"] / dims["n_router"] == 24


def test_the_traffic_is_reason_offlines_requests(sized):
    cell = spec.load_cell(CELL)
    other = spec.load_cell("kexaone.reason-offline")
    assert cell.kind == "serve-closed-blocks"
    assert {k: v for k, v in cell.traffic.items()
            if k not in ("kind", "what")} == {
                k: v for k, v in other.traffic.items()
                if k not in ("kind", "what")}
    assert cell.traffic["clients"] == cell.config["engine"]["num_slots"]
    from benchmark import traffic_gen
    mine = traffic_gen.generate(cell.traffic, 5, 51, 1000)
    theirs = traffic_gen.generate(other.traffic, 5, 51, 1000)
    assert mine == theirs              # the same requests, token for token


# ------------------------------------------------ entries and readers

NEW = ("tokens_per_pass", "commit_pass_pct", "denoise_launch_p50_ms",
       "denoise_step_roofline", "gqa_paged_decode_roofline",
       "head_confidence_share_pct")
SIBLINGS = ("expert_rows_per_expert", "batch_occupancy_pct",
            "hbm_peak_pct", "kv_pages_peak_pct", "step_host_p50_ms",
            "host_behind_pct", "prefill_device_share_pct",
            "idle_step_loop_pct", "idle_admit_pct")
NEW_READERS = {"rows_sum_ratio", "launch_tail_share"}


def test_every_entry_it_brought_lists_its_cell_alone():
    cell = spec.load_cell(CELL)
    assert {m["name"] for m in cell.per_layer} == {
        f"{name}.sdar" for name in NEW + SIBLINGS}
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "setup_s"}
    for metric in cell.per_layer:
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "serve_tokens_per_s"
    for other in BENCH["workloads"]:
        if other["name"] != CELL:
            assert not any(
                m["name"].endswith(".sdar")
                for m in spec.load_cell(other["name"]).per_layer)
    # appended at the end of their lists
    assert BENCH["workloads"][-1]["name"] == CELL
    assert BENCH["configs"][-1]["name"] == CONFIG
    assert [m["name"] for m in BENCH["per_layer"][-15:]] == [
        m["name"] for m in cell.per_layer]


@pytest.mark.parametrize("name", NEW + SIBLINGS)
def test_each_metric_has_its_definition(name):
    definition = spec.layer_metric_file(f"{name}.sdar")
    entry = next(m for m in BENCH["per_layer"]
                 if m["name"] == f"{name}.sdar")
    for key in ("unit", "better", "source", "layer", "moves"):
        assert definition[key] == entry[key]
    layers = {m["layer"] for m in BENCH["per_layer"]
              if not m["name"].endswith(".sdar")}
    assert entry["layer"] in layers         # a layer PERF.md has
    before = {spec.layer_metric_file(m["name"])["reader"]
              for m in BENCH["per_layer"]
              if not m["name"].endswith(".sdar")}
    assert definition["reader"] in before | NEW_READERS
    assert (definition["reader"] in NEW_READERS) == (
        name in ("tokens_per_pass", "commit_pass_pct",
                 "head_confidence_share_pct"))


def test_rows_sum_ratio_by_hand():
    reader = _load("layer_metrics/readers/rows_sum_ratio.py")
    rows = [{"block_tokens_landed": 300, "block_denoise_passes": 300,
             "block_commit_passes": 76},
            {"block_tokens_landed": 320, "block_denoise_passes": 310,
             "block_commit_passes": 80},
            {"slots_active": 3}]            # a row of another program
    passes = {"whole": ["block_denoise_passes", "block_commit_passes"]}
    assert reader.value(rows, {"part": ["block_tokens_landed"],
                               **passes, "scale": 1}) == 620 / 766
    assert reader.value(rows, {"part": ["block_commit_passes"],
                               **passes, "scale": 100}) == \
        pytest.approx(100 * 156 / 766)
    # a program that writes no such attrs (the parent): nothing to read
    assert reader.value(rows[2:], {"part": ["block_tokens_landed"],
                                   **passes}) is None
    assert reader.read({"step_rows": ([], 0.0)},
                       {"part": ["a"], "whole": ["b"]}) is None


def test_launch_tail_share_by_hand():
    reader = _load("layer_metrics/readers/launch_tail_share.py")
    head = "%fusion.9 = bf16[384,151936] fusion(%x, " \
           "%params__lm_head____kernel__.1)"

    def plane(events, programs):
        return {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": programs},
            {"name": "XLA Ops", "events": events}]}

    trace = {"planes": [plane(
        [("%fusion.1 = f32[4] fusion(%a)", 0, 8000),
         # a prefetch of the head's weight, issued early and short
         ("%slice-start.3 = bf16[2048,128] slice-start("
          "%params__lm_head____kernel__.1)", 1000, 5),
         (head, 8000, 1500),
         ("%fusion.11 = f32[384] fusion(%fusion.9)", 9500, 500),
         # the next launch: the head starts 9 us in
         (head, 21000, 1500)],
        [("jit__decode_step(123)", 0, 10000),
         ("jit__decode_step(123)", 12000, 10500),
         ("jit__prefill_paged(5)", 30000, 9000)])]}
    share = reader.tail_share(trace, "_decode_step",
                              "params__lm_head____kernel__")
    assert share == pytest.approx(100 * (2000 + 1500) / (10000 + 10500))
    assert reader.tail_share(trace, "_decode_step", "no_such") is None
    assert reader.read({"profile": None}, {}) is None


def test_the_block_pass_work_by_hand(sized):
    _config, _module, dims = sized
    step = _load("kernels/denoise_step.py").step_work(
        dims, slots=96.0, hit=760.0, pairs=18432.0, keys=160000.0)
    always = (151936 * 2048 + 6 * (2048 * 5120 + 4096 * 2048)
              + 6 * 2048 * 128)
    assert step["bytes"] == 2.0 * (always + 3 * 2048 * 768 * 760
                                   + 4 * 2048 * 96) \
        + 2048 * 6 * 160000.0
    assert step["flops"] == 2.0 * (always * 4 * 96
                                   + 3 * 2048 * 768 * 18432) \
        + 4.0 * 32 * 128 * 4 * 6 * 160000.0
    # the pass is memory-bound on a v5e
    assert step["bytes"] / 819e9 > step["flops"] / 197e12
    call = _load("kernels/paged_decode_block.py").call_work(
        160000.0, 96.0, 4, 32, 4, 128)
    assert call["bytes"] == 2.0 * 160000 * 512 * 2 \
        + 2.0 * 4 * 96 * 4096 * 2
    assert call["flops"] == 4.0 * 160000 * 32 * 128 * 4


def test_work_functions_read_none_without_block_rows(sized):
    _config, _module, dims = sized
    obs = {"dims": dims, "profile": {"started": 0.0, "stopped": 9.0},
           "out_dir": "/nonexistent",
           "step_rows": ([{"mono_start": 1.0, "slots_active": 90,
                           "expert_pairs_chosen": 10, "experts_hit": 5,
                           "expert_pairs_here": 10,
                           "live_tokens": 1000}], 9.0)}
    # rows of a program that counts no block passes (the parent's)
    assert _load("kernels/denoise_step.py").work(
        obs, {"program": 3}) is None
    assert _load("kernels/paged_decode_block.py").work(
        obs, {"decode": 18}) is None
    obs["step_rows"][0][0].update(block_commit_passes=20)
    work = _load("kernels/denoise_step.py").work(obs, {"program": 3})
    assert work["bytes"] > 0 and work["flops"] > 0
    one = _load("kernels/paged_decode_block.py").work(obs, {"decode": 6})
    assert one["bytes"] == 6 * (2.0 * 1000 * 512 * 2
                                + 2.0 * 4 * 90 * 4096 * 2)


# ------------------------------------------------ the reference


@pytest.fixture(scope="module")
def tiny():
    """The configuration at its rehearse_tiny size: (file, module,
    dims, bfloat16 params as a run makes them)."""
    config = harness.merged(spec.load_config(CONFIG), True)
    module = spec.load_model(config)
    dims = module.dims(config)
    params = weights.make_params(module.param_leaves(dims), 3,
                                 jnp.bfloat16)
    return config, module, dims, params


def test_one_forward_is_the_naive_pass_by_pass_loop(tiny):
    """The reference lays every pass of every block side by side in
    one sequence under one mask. The naive loop: for each block and
    each pass, the clean blocks before it and the block as that pass
    read it, as ONE ordinary block-causal sequence (no copies), whose
    last block's hidden states must be the side-by-side forward's."""
    _config, module, dims, params = tiny
    rng = np.random.default_rng(2)
    block, steps, mask = dims["block"], dims["steps"], dims["mask_id"]
    start, total = 8, 20
    clean = rng.integers(1, 250, total).astype(np.int32)
    at = np.concatenate([
        [steps, steps, 1, 0],               # two given, two passes
        rng.permutation(steps), [0, 0, 1, 1]]).astype(np.int32)
    sizes = dict(layers=dims["published_layers"], block=block,
                 q_heads=dims["n_heads"], kv_heads=dims["n_kv_heads"],
                 theta=dims["theta"], top_k=dims["top_k"],
                 eps=dims["eps"])
    tokens, positions, copies = plain.extended(clean, start, at, steps,
                                               mask)
    assert len(tokens) == total + steps * (total - start)
    hidden, _ = plain.stack_hidden(params, tokens, positions, copies,
                                   **sizes)
    compared = 0
    for first in range(start, total, block):
        mine = at[first - start:first - start + block]
        for s in range(steps):
            noisy = np.where((mine < s) | (mine >= steps),
                             clean[first:first + block], mask)
            sequence = np.concatenate([clean[:first], noisy]).astype(
                np.int32)
            naive, _ = plain.stack_hidden(
                params, sequence, np.arange(len(sequence),
                                            dtype=np.int32),
                np.full((len(sequence),), plain.CLEAN, np.int32),
                **sizes)
            rows = [plain.extended_row(first + i, s, total, start)
                    for i in range(block)]
            np.testing.assert_allclose(
                np.asarray(hidden)[rows], np.asarray(naive)[-block:],
                atol=2e-4, rtol=2e-4)
            compared += 1
    assert compared == 3 * steps
    # and the clean rows are the plain block-causal forward
    naive, _ = plain.stack_hidden(
        params, clean, np.arange(total, dtype=np.int32),
        np.full((total,), plain.CLEAN, np.int32), **sizes)
    np.testing.assert_allclose(np.asarray(hidden)[:total],
                               np.asarray(naive), atol=2e-4, rtol=2e-4)


def test_the_one_mask_by_hand():
    pos = jnp.asarray([0, 1, 4, 5, 4, 5, 4, 5])
    copy = jnp.asarray([-1, -1, -1, -1, 0, 0, 1, 1])
    got = np.asarray(plain.visible(pos, copy, pos, copy, 4)).astype(int)
    assert got.tolist() == [
        [1, 1, 0, 0, 0, 0, 0, 0],       # clean, block 0
        [1, 1, 0, 0, 0, 0, 0, 0],
        [1, 1, 1, 1, 0, 0, 0, 0],       # clean, block 1
        [1, 1, 1, 1, 0, 0, 0, 0],
        [1, 1, 0, 0, 1, 1, 0, 0],       # copy 0 of block 1
        [1, 1, 0, 0, 1, 1, 0, 0],
        [1, 1, 0, 0, 0, 0, 1, 1],       # copy 1 of block 1
        [1, 1, 0, 0, 0, 0, 1, 1]]


def test_unmask_slacks_by_hand(tiny):
    _config, module, dims, _params = tiny
    log = np.log
    confidence = np.full((4, 8), np.nan)
    # block 0: passes 0..3 took positions 2, 0, 3, 1
    confidence[0, :4] = log([0.3, 0.1, 0.5, 0.2])
    confidence[1, :4] = log([0.4, 0.1, 0.9, 0.45])    # 3 above 0
    confidence[2, :4] = log([0.9, 0.2, 0.9, 0.3])
    confidence[3, :4] = log([0.9, 0.2, 0.9, 0.9])
    # block 1: one pass took all four (the dynamic rule), one of them
    # under the threshold
    confidence[0, 4:] = log([0.95, 0.99, 0.8, 0.93])
    at = np.asarray([1, 3, 0, 2, 0, 0, 0, 0])
    static = {**dims, "remask": "low_confidence_static"}
    got = module.unmask_slacks(confidence, at, static)
    assert set(got) == {(0, 0), (0, 1), (0, 2), (0, 3), (4, 0)}
    assert got[(0, 0)] == 0 and got[(0, 2)] == 0 and got[(0, 3)] == 0
    assert got[(0, 1)] == pytest.approx(log(0.45) - log(0.4))
    assert got[(4, 0)] == 0             # the four of four: the top four
    dynamic = {**dims, "remask": "low_confidence_dynamic",
               "threshold": 0.9}
    got = module.unmask_slacks(confidence, at, dynamic)
    assert got[(4, 0)] == pytest.approx(log(0.9) - log(0.8))
    assert got[(0, 0)] == 0


# ------------------------------------------------ the check


class _Served:
    """Requests through the engine as a run builds it, and the
    driver's own check over them (benchmark/drivers/
    serve_closed_blocks.py::Session.check), without the front end."""

    def __init__(self, tiny, **control):
        from batch_shipyard_tpu.models.serving import Request
        self.driver = _load("drivers/serve_closed_blocks.py")
        config, module, dims, params = tiny
        self.engine = serve.build_engine(module, config, params,
                                         **control)
        rng = np.random.default_rng(11)
        self.rows = []
        for idx in range(24):
            prompt = [int(t) for t in rng.integers(
                1, dims["vocab"], int(rng.integers(8, 64)))]
            self.rows.append({"idx": idx, "prompt": prompt,
                              "in_window": True, "ok": True})
            self.engine.submit(Request(
                f"bench-{idx}", prompt, int(rng.integers(5, 40))))
        done = {}
        while self.engine.pending():
            for request_id, tokens in self.engine.step():
                done[request_id] = tokens
        for row in self.rows:
            row["tokens"] = done[f"bench-{row['idx']}"]
            row["decisions"] = self.engine.take_decisions(
                f"bench-{row['idx']}")
        self.tiny = tiny

    def numbers(self, corruption=None, rows=None) -> dict:
        config, module, dims, params = self.tiny
        session = self.driver.Session.__new__(self.driver.Session)
        session.ctx = types.SimpleNamespace(seed=5)
        session.model, session.model_module = config, module
        session.dims, session.params = dims, params
        session.decision_layers = spec.decision_layers(module, config,
                                                       dims)
        session.corruption = corruption
        return session.check(self.rows if rows is None else rows)


@pytest.fixture(scope="module")
def sound(tiny):
    return _Served(tiny)


def _judge(tiny, numbers):
    return check.judge(numbers, tiny[0]["check"]["limits"])


def test_the_sound_engine_is_correct_by_its_own_check(tiny, sound):
    checked = sound.numbers()
    ok, lines = _judge(tiny, checked["numbers"])
    assert ok, lines
    readings = checked["readings"]
    assert checked["requests"] == 24
    assert checked["tokens"] == sum(len(r["tokens"]) for r in sound.rows)
    assert readings["requests_without_record"] == 0
    # every pass judged: 2 routed layers x (the writing pass + 4
    # denoise passes) a recorded position, a choice a denoise pass
    assert len(readings["slack"]) > 5 * checked["tokens"]
    assert len(readings["unmask_slack"]) >= checked["tokens"]
    assert sum(g == 0.0 for g in readings["gaps"]) > \
        0.9 * checked["tokens"]


@pytest.mark.parametrize("corruption, number", [
    ({"reroute_share": 0.01}, "routing_rejected_share"),
    ({"reroute_share": 0.01}, "routing_slack_tail_mean"),
    ({"shift_unmask_share": 0.01}, "unmask_rejected_share"),
    ({"shift_unmask_share": 0.01}, "gap_tail_mean")])
def test_a_corrupted_record_reads_over_its_limit(tiny, sound, corruption,
                                                 number):
    numbers = sound.numbers(corruption)["numbers"]
    ok, lines = _judge(tiny, numbers)
    assert not ok, lines
    limit = tiny[0]["check"]["limits"][number]
    assert numbers[number] > limit
    assert numbers[number] > 3 * max(
        sound.numbers()["numbers"][number], 1e-9)


def test_a_request_without_a_record_fails(tiny, sound):
    rows = [dict(row) for row in sound.rows]
    rows[3]["decisions"] = None
    numbers = sound.numbers(rows=rows)["numbers"]
    assert numbers["routing_rejected_share"] is None
    assert numbers["routing_slack_tail_mean"] is None
    assert numbers["unmask_rejected_share"] is None
    assert not _judge(tiny, numbers)[0]


def test_a_mask_kept_causal_inside_a_block_reads_over_its_limits(tiny,
                                                                 sound):
    """The control that is a program: BlockDiffusion.bidirectional
    False (program_model's causal_inside_block), prefill and block
    pass. The attention's softmax kept in bfloat16 is NOT a control at
    this size (contexts under a key block: the running terms are
    rounded once); the configuration's file has its reading at the
    cell's own load."""
    control = _Served(tiny, causal_inside_block=True)
    assert control.engine.config.attend_block == 0
    assert sound.engine.config.attend_block == 4
    numbers = control.numbers()["numbers"]
    ok, lines = _judge(tiny, numbers)
    assert not ok, lines
    base = sound.numbers()["numbers"]
    assert numbers["gap_tail_mean"] > 3 * base["gap_tail_mean"]
    assert numbers["gap_tail_mean"] > \
        tiny[0]["check"]["limits"]["gap_tail_mean"]


def test_the_harnesss_own_call_agrees_where_it_can(tiny, sound):
    """teacher_forced_logits behind check.serve_gaps (prompt +
    served[:-1], row r for token r + 1): every token of a block that
    lies whole inside that sequence reads the gap request_readings
    reads for it."""
    config, module, dims, params = tiny
    layers = spec.decision_layers(module, config, dims)
    request = max(sound.rows, key=lambda r: len(r["tokens"]))
    theirs = check.serve_gaps(params, module, config, dims, [request],
                              layers)
    mine = module.request_readings(
        params, request["prompt"], request["tokens"],
        request["decisions"], config, dims)
    whole = (len(request["prompt"]) + len(request["tokens"]) - 1) \
        // dims["block"] * dims["block"] - len(request["prompt"])
    assert whole > 8
    np.testing.assert_allclose(theirs["gaps"][:whole],
                               mine["gaps"][:whole], atol=1e-3)
    assert theirs["requests_without_record"] == 0
    assert np.isfinite(theirs["slack"]).all()

"""The plain reference agrees with the program at a tiny size on the
CPU (prefill then decode through the paged cache, cold and
prefix-shared), and the comparison that decides ``correct`` has been
shown to fail: for the control, which is the program's own int8 KV
cache switched on, and for ONE token altered where it is produced."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, flops, weights
from benchmark.drivers import serve
from benchmark.reference import plain

MODEL = {"hidden_size": 128, "num_attention_heads": 2,
         "intermediate_size": 256, "num_hidden_layers": 4,
         "vocab_size": 2048, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
         "engine": {"num_slots": 8, "max_decode_len": 320,
                    "kv_page_size": 16, "kv_num_pages": 200}}
DIMS = flops.model_dims(MODEL)
REQUESTS, NEW_TOKENS = 96, 128      # 12,288 served tokens, as a window
TAIL_FROM = 0.03
# Readings at this size (CPU, seeds 1-3, 12,288 tokens each): the
# bfloat16 engine's gap_tail_mean 6.6e-6, 9.5e-6, 10.2e-6; with its
# int8 KV cache switched on 33.0e-6, 102.4e-6, 38.5e-6. At 2,048
# tokens the two overlap (2.6e-6-16.4e-6 against 10.2e-6-124e-6): the
# statistic needs a window's worth of tokens to resolve an int8 cache.
LIMITS = {"gap_tail_mean": 1.8e-5}


def _serve(seed, kv_cache_dtype=None):
    from batch_shipyard_tpu.models.serving import Request
    params = weights.make_params(DIMS, seed, jnp.bfloat16)
    engine = serve.build_engine(None, MODEL, params, kv_cache_dtype)
    rng = random.Random(seed)
    prefix = [rng.randrange(1, DIMS["vocab"]) for _ in range(32)]
    prompts = {}
    for i in range(REQUESTS):
        prompts[f"r{i}"] = prefix + [
            rng.randrange(1, DIMS["vocab"])
            for _ in range(rng.randrange(16, 120))]
        engine.submit(Request(request_id=f"r{i}", prompt=prompts[f"r{i}"],
                              max_new_tokens=NEW_TOKENS))
    done = {}
    while engine.pending():
        for request_id, tokens in engine.step():
            done[request_id] = tokens
    finished = [{"idx": i, "prompt": prompts[r], "tokens": done[r]}
                for i, r in enumerate(sorted(done))]
    return params, engine, finished


def _numbers(readings):
    return check.gap_numbers(readings["gaps"], TAIL_FROM)


@pytest.fixture(scope="module")
def served():
    params, engine, finished = _serve(seed=1)
    return params, engine, finished, check.serve_gaps(
        params, DIMS, MODEL, finished)


def test_paged_prefill_and_decode_agree_with_the_reference(served):
    _params, engine, finished, readings = served
    # every served token of every finished request is read
    assert len(readings["gaps"]) == REQUESTS * NEW_TOKENS
    assert readings["requests"] == REQUESTS
    assert set(readings["request"]) == {r["idx"] for r in finished}
    assert engine.prefix_stats()["hit_tokens"] > 0    # shared path too
    ok, lines = check.judge(_numbers(readings), LIMITS)
    assert ok, lines
    # most served tokens ARE the reference's best
    assert sum(1 for g in readings["gaps"] if g == 0.0) > \
        0.9 * len(readings["gaps"])


def test_paged_prefill_logits_match_the_reference_directly(served):
    params, engine, finished, _readings = served
    prompt = finished[0]["prompt"]
    bucket = engine._bucket_length(len(prompt))
    padded = jnp.asarray([prompt + [0] * (bucket - len(prompt))],
                         jnp.int32)
    row = np.full((engine.max_blocks,), engine._scratch_page, np.int32)
    _cache, last = engine._prefill_paged(
        params, engine.cache, 0, padded, jnp.asarray(row), len(prompt))
    tokens = jnp.asarray(prompt + [0] * (-len(prompt) % 64), jnp.int32)
    want = plain.teacher_forced_logits(
        params, tokens, jnp.asarray([len(prompt) - 1]),
        n_layers=DIMS["n_layers"], n_heads=DIMS["n_heads"], eps=1e-6,
        theta=10000.0)[0]
    error = float(jnp.linalg.norm(last - want) / jnp.linalg.norm(want))
    # bfloat16 activations against float32: a few parts in a thousand
    assert error < 0.02, error
    assert int(jnp.argmax(want)) == int(jnp.argmax(last)) or \
        float(jnp.max(want) - want[int(jnp.argmax(last))]) < 0.05


def test_the_programs_int8_kv_cache_comes_out_not_correct(served):
    """The control: the same engine, weights and requests with the
    program's own lower-precision path, kv_cache_dtype="int8"."""
    sound = _numbers(served[3])
    params, _engine, finished = _serve(seed=1, kv_cache_dtype="int8")
    control = _numbers(check.serve_gaps(params, DIMS, MODEL, finished))
    ok, lines = check.judge(control, LIMITS)
    assert not ok, lines
    assert control["gap_tail_mean"] > 3 * sound["gap_tail_mean"]


def test_one_token_altered_where_it_is_produced_is_caught(served):
    params, _engine, finished, readings = served
    longest = max(finished, key=lambda r: len(r["prompt"]))
    broken = dict(longest, tokens=list(longest["tokens"]))
    broken["tokens"][5] = (broken["tokens"][5] + 977) % 2048
    altered = check.serve_gaps(params, DIMS, MODEL, [broken])
    assert max(altered["gaps"]) > 1.0
    # ... as the one wrong token among the whole window's
    gaps = [g for g, idx in zip(readings["gaps"], readings["request"])
            if idx != longest["idx"]] + altered["gaps"]
    assert len(gaps) == REQUESTS * NEW_TOKENS
    ok, _lines = check.judge(check.gap_numbers(gaps, TAIL_FROM), LIMITS)
    assert not ok


def test_gap_numbers_by_hand():
    numbers = check.gap_numbers([0.0, 0.03, 0.05, 0.14], 0.04)
    assert numbers["gap_tail_mean"] == pytest.approx((0.01 + 0.10) / 4)
    assert numbers["gap_max"] == 0.14
    assert numbers["gap_mean"] == pytest.approx(0.055)
    assert check.gap_numbers([], 0.04)["gap_tail_mean"] is None


def test_judge_fails_a_missing_or_infinite_number():
    assert check.judge({"gap_tail_mean": None}, LIMITS)[0] is False
    assert check.judge({"gap_tail_mean": float("nan")}, LIMITS)[0] \
        is False
    assert check.judge({"gap_tail_mean": 1e-6, "gap_max": 9.0},
                       LIMITS)[0]      # gap_max has no limit

"""The plain reference of EVERY configuration of BENCHMARK.json agrees
with the program at a tiny size on the CPU (prefill then decode through
the paged cache, cold and prefix-shared), and the comparison that
decides ``correct`` has been shown to fail: for the control, which is
the program's own lower-precision path switched on (the int8 KV
cache), and for ONE token altered where it is produced.

One case per configuration: the model module is the one its file
names, and the sizes, the load and the limit are read from
tests/benchmark/reference_cases/<configuration>.json, so that a
configuration a later PR adds is tested by adding that file."""

import json
import pathlib
import random

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, harness, spec, weights
from benchmark.drivers import serve

CASES = pathlib.Path(__file__).resolve().parent / "reference_cases"
CONFIGS = [c["name"] for c in spec.load_benchmark()["configs"]]


class Case:
    """A configuration at its reference case's sizes."""

    def __init__(self, config_name: str) -> None:
        path = CASES / f"{config_name}.json"
        assert path.is_file(), (
            f"configuration {config_name} has no reference case: add "
            f"{path.relative_to(spec.ROOT)}")
        with open(path, encoding="utf-8") as fh:
            self.data = json.load(fh)
        self.model = harness.merged(
            dict(spec.load_config(config_name),
                 rehearse_tiny=self.data["sizes"]), True)
        self.module = spec.load_model(self.model)
        self.dims = self.module.dims(self.model)
        self.leaves = self.module.param_leaves(self.dims)
        self.tokens = self.data["requests"] * self.data["new_tokens"]
        self.limits = self.data["limits"]

    def serve(self, kv_cache_dtype=None):
        """-> (params, engine, finished): every request of the case
        through the engine as a run builds it."""
        from batch_shipyard_tpu.models.serving import Request
        data, seed, vocab = self.data, self.data["seed"], \
            self.dims["vocab"]
        params = weights.make_params(self.leaves, seed, jnp.bfloat16)
        engine = serve.build_engine(self.module, self.model, params,
                                    kv_cache_dtype)
        rng = random.Random(seed)
        prefix = [rng.randrange(1, vocab)
                  for _ in range(data["shared_prefix_tokens"])]
        prompts = {}
        for i in range(data["requests"]):
            prompts[f"r{i}"] = prefix + [
                rng.randrange(1, vocab)
                for _ in range(rng.randrange(
                    data["prompt_tokens"]["min"],
                    data["prompt_tokens"]["below"]))]
            engine.submit(Request(
                request_id=f"r{i}", prompt=prompts[f"r{i}"],
                max_new_tokens=data["new_tokens"]))
        done = {}
        while engine.pending():
            for request_id, tokens in engine.step():
                done[request_id] = tokens
        finished = [{"idx": i, "prompt": prompts[r], "tokens": done[r]}
                    for i, r in enumerate(sorted(done))]
        return params, engine, finished

    def gaps(self, params, finished) -> dict:
        return check.serve_gaps(params, self.module, self.model,
                                self.dims, finished)

    def numbers(self, readings) -> dict:
        return check.gap_numbers(readings["gaps"],
                                 self.data["tail_from"])


@pytest.fixture(scope="module", params=CONFIGS)
def served(request):
    case = Case(request.param)
    params, engine, finished = case.serve()
    return case, params, engine, finished, case.gaps(params, finished)


def test_paged_prefill_and_decode_agree_with_the_reference(served):
    case, _params, engine, finished, readings = served
    # every served token of every finished request is read
    assert len(readings["gaps"]) == case.tokens
    assert readings["requests"] == case.data["requests"]
    assert set(readings["request"]) == {r["idx"] for r in finished}
    assert engine.prefix_stats()["hit_tokens"] > 0    # shared path too
    ok, lines = check.judge(case.numbers(readings), case.limits)
    assert ok, lines
    # most served tokens ARE the reference's best
    assert sum(1 for g in readings["gaps"] if g == 0.0) > \
        0.9 * len(readings["gaps"])


def test_paged_prefill_logits_match_the_reference_directly(served):
    case, params, engine, finished, _readings = served
    prompt = finished[0]["prompt"]
    bucket = next(b for b in engine.warmup_buckets()
                  if b >= len(prompt))
    padded = jnp.asarray([prompt + [0] * (bucket - len(prompt))],
                         jnp.int32)
    row = np.full((engine.max_blocks,), engine._scratch_page, np.int32)
    _cache, last = engine._prefill_paged(
        params, engine.cache, 0, padded, jnp.asarray(row), len(prompt))
    tokens = jnp.asarray(prompt + [0] * (-len(prompt) % 64), jnp.int32)
    want = case.module.teacher_forced_logits(
        params, tokens, jnp.asarray([len(prompt) - 1]), case.model,
        case.dims)[0]
    error = float(jnp.linalg.norm(last - want) / jnp.linalg.norm(want))
    # bfloat16 activations against float32: a few parts in a thousand
    assert error < 0.02, error
    assert int(jnp.argmax(want)) == int(jnp.argmax(last)) or \
        float(jnp.max(want) - want[int(jnp.argmax(last))]) < 0.05


def test_the_programs_int8_kv_cache_comes_out_not_correct(served):
    """The control: the same engine, weights and requests with the
    program's own lower-precision path (the case's ``control``:
    kv_cache_dtype="int8")."""
    case = served[0]
    sound = case.numbers(served[4])
    params, _engine, finished = case.serve(**case.data["control"])
    control = case.numbers(case.gaps(params, finished))
    ok, lines = check.judge(control, case.limits)
    assert not ok, lines
    assert control["gap_tail_mean"] > 3 * sound["gap_tail_mean"]


def test_one_token_altered_where_it_is_produced_is_caught(served):
    case, params, _engine, finished, readings = served
    longest = max(finished, key=lambda r: len(r["prompt"]))
    broken = dict(longest, tokens=list(longest["tokens"]))
    broken["tokens"][5] = \
        (broken["tokens"][5] + 977) % case.dims["vocab"]
    altered = case.gaps(params, [broken])
    assert max(altered["gaps"]) > 1.0
    # ... as the one wrong token among the whole window's
    gaps = [g for g, idx in zip(readings["gaps"], readings["request"])
            if idx != longest["idx"]] + altered["gaps"]
    assert len(gaps) == case.tokens
    ok, _lines = check.judge(
        check.gap_numbers(gaps, case.data["tail_from"]), case.limits)
    assert not ok


def test_gap_numbers_by_hand():
    numbers = check.gap_numbers([0.0, 0.03, 0.05, 0.14], 0.04)
    assert numbers["gap_tail_mean"] == pytest.approx((0.01 + 0.10) / 4)
    assert numbers["gap_max"] == 0.14
    assert numbers["gap_mean"] == pytest.approx(0.055)
    assert check.gap_numbers([], 0.04)["gap_tail_mean"] is None


def test_judge_fails_a_missing_or_infinite_number():
    limits = {"gap_tail_mean": 1.8e-5}
    assert check.judge({"gap_tail_mean": None}, limits)[0] is False
    assert check.judge({"gap_tail_mean": float("nan")}, limits)[0] \
        is False
    assert check.judge({"gap_tail_mean": 1e-6, "gap_max": 9.0},
                       limits)[0]      # gap_max has no limit

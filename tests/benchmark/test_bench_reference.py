"""The plain reference of EVERY configuration of BENCHMARK.json agrees
with the program at a tiny size on the CPU (prefill then decode through
the paged cache, cold and prefix-shared), and the comparison that
decides ``correct`` has been shown to fail: for the case's control
(keyword overrides for the module's program_model: the program's own
lower-precision path switched on, for Baichuan the int8 KV cache; or,
under "decisions", a corruption of the engine's record of its
choices), and for ONE token altered where it is produced.

One case per configuration: the model module is the one its file
names, and the sizes, the load, the limits and the control are read
from tests/benchmark/reference_cases/<configuration>.json, so that a
configuration a later PR adds is tested by adding that file. A module
that declares decisions (spec.decision_layers) is judged on the
engine's own choices (engine.take_decisions), as a run judges it. The
one test that reaches past the engine's public surface is generated
only for a case that asks for it under "direct"."""

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


def _case_data(config_name: str) -> dict:
    path = CASES / f"{config_name}.json"
    assert path.is_file(), (
        f"configuration {config_name} has no reference case: add "
        f"{path.relative_to(spec.ROOT)}")
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


# the configurations whose case asks for the comparison that calls the
# engine's prefill by its private name
DIRECT_PREFILL = [name for name in CONFIGS if "prefill_logits"
                  in _case_data(name).get("direct", {})]


class Case:
    """A configuration at its reference case's sizes."""

    def __init__(self, config_name: str) -> None:
        self.data = _case_data(config_name)
        self.model = harness.merged(
            dict(spec.load_config(config_name),
                 rehearse_tiny=self.data["sizes"]), True)
        self.module = spec.load_model(self.model)
        self.dims = self.module.dims(self.model)
        self.leaves = self.module.param_leaves(self.dims)
        self.layers = spec.decision_layers(self.module, self.model,
                                           self.dims)
        self.tokens = self.data["requests"] * self.data["new_tokens"]
        self.limits = self.data["limits"]

    def serve(self, **control):
        """-> (params, engine, finished): every request of the case
        through the engine as a run builds it, with a control's
        overrides."""
        from batch_shipyard_tpu.models.serving import Request
        data, seed, vocab = self.data, self.data["seed"], \
            self.dims["vocab"]
        params = weights.make_params(self.leaves, seed, jnp.bfloat16)
        record_control = control.pop("decisions", None)
        engine = serve.build_engine(self.module, self.model, params,
                                    **control)
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
        finished = {r: {"idx": i, "prompt": prompts[r],
                        "tokens": done[r]}
                    for i, r in enumerate(sorted(done))}
        serve.take_decisions(engine, self.layers, finished,
                             record_control, seed)
        return params, engine, list(finished.values())

    def gaps(self, params, finished) -> dict:
        return check.serve_gaps(params, self.module, self.model,
                                self.dims, finished, self.layers)

    def numbers(self, readings) -> dict:
        numbers = check.gap_numbers(readings["gaps"],
                                    self.data["tail_from"])
        if self.layers:
            numbers.update(check.routing_numbers(
                readings, self.data["slack_from"]))
        return numbers


_SERVED: dict = {}


def _served(config_name: str):
    if config_name not in _SERVED:
        case = Case(config_name)
        params, engine, finished = case.serve()
        _SERVED[config_name] = (case, params, engine, finished,
                                case.gaps(params, finished))
    return _SERVED[config_name]


@pytest.fixture(scope="module", params=CONFIGS)
def served(request):
    return _served(request.param)


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


@pytest.mark.parametrize("config_name", DIRECT_PREFILL)
def test_paged_prefill_logits_match_the_reference_directly(config_name):
    """Past the engine's public surface (engine._prefill_paged with
    engine._scratch_page), so only for a case that names it under
    "direct", with the tolerance and its reason stated there: a
    program PR that reshapes the prefill takes the name out of a case
    of its own, or the next benchmark PR does."""
    case, params, engine, finished, _readings = _served(config_name)
    tolerance = case.data["direct"]["prefill_logits"]["relative_error"]
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
    assert error < tolerance, error
    assert int(jnp.argmax(want)) == int(jnp.argmax(last)) or \
        float(jnp.max(want) - want[int(jnp.argmax(last))]) < 0.05


def test_the_programs_int8_kv_cache_comes_out_not_correct(served):
    """The control: the same engine, weights and requests with the
    case's ``control`` (one set of overrides, or a list of them; for
    Baichuan the program's own lower-precision path,
    kv_cache_dtype="int8"). Each has to fail a limit, by a number at
    least three times the sound reading."""
    case = served[0]
    sound = case.numbers(served[4])
    for overrides in check.controls(case.data["control"]):
        params, _engine, finished = case.serve(**overrides)
        control = case.numbers(case.gaps(params, finished))
        ok, lines = check.judge(control, case.limits)
        assert not ok, (overrides, lines)
        assert any(control[name] is None or
                   (control[name] > limit
                    and control[name] > 3 * sound[name])
                   for name, limit in case.limits.items()), lines


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

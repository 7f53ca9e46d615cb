"""The reference check holds a ROUTED architecture (top-k experts):
shown on a stand-in that lives under tests/benchmark only (model
module models/routed_standin.py, float32 reference
reference/routed_standin_plain.py, bfloat16 program
models/routed_standin_program.py, which yields greedy tokens and its
choices), on the CPU.

Unforced, a float32 reference and a sound bfloat16 program disagree
about close k-th and (k+1)-th scores and the gap is blind (a); on the
program's own choices the gap is back at its floor (b) and a wrong
equation shows (c); the choices are not taken on trust, the reference
judges each by its own scores (d, the record's control); what was not
recorded is counted (e); no record is not correct (f); the bound on
the check's cost takes the same requests every time (g); and a module
that declares no decisions is judged byte for byte as before (h).

The sizes, tail_from, slack_from, the limits and the controls are the
``check`` section of configs/routed-standin-serve-1chip.json, which
says what readings they were set from."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, harness, spec, weights

BENCH = spec.load_benchmark()
CONFIG = json.loads((
    spec.ROOT / "tests/benchmark/configs/routed-standin-serve-1chip.json"
).read_text())
SEED, REQUESTS, PROMPT, NEW = 1, 16, 32, 96


class Standin:
    def __init__(self) -> None:
        self.config = harness.merged(CONFIG, False)
        self.module = spec.load_model(self.config)
        self.program = spec.load_module(
            spec.ROOT, BENCH, "models/routed_standin_program.py")
        self.dims = self.module.dims(self.config)
        self.layers = spec.decision_layers(self.module, self.config,
                                           self.dims)
        self.section = self.config["check"]
        self.params = weights.make_params(
            self.module.param_leaves(self.dims), SEED, jnp.bfloat16)
        self.prompts = np.random.default_rng(SEED).integers(
            1, self.dims["vocab"], (REQUESTS, PROMPT))

    def serve(self, **control) -> list:
        """The finished requests of the stand-in's program with a
        control's overrides, each with the record of its choices, as a
        run's rows carry them."""
        control = dict(control)
        record_control = control.pop("decisions", None)
        options = self.module.program_model(
            self.config, self.dims, self.config["engine"], **control)
        served, chosen = self.program.generate(
            self.params, jnp.asarray(self.prompts, jnp.int32), NEW,
            seed=SEED, **options)
        served = np.asarray(served)
        chosen = {name: np.asarray(rows)
                  for name, rows in chosen.items()}
        finished = []
        for i in range(REQUESTS):
            record = {"first": 0, "layers": {
                name: rows[i] for name, rows in chosen.items()}}
            if record_control:
                record = check.reroute(
                    record, self.layers,
                    record_control["reroute_share"], SEED + i)
            finished.append({"idx": i, "prompt": self.prompts[i].tolist(),
                             "tokens": served[i].tolist(),
                             "decisions": record})
        return finished

    def read(self, finished, layers=None, **kwargs):
        """-> (numbers, readings) as drivers/serve.py makes them."""
        layers = self.layers if layers is None else layers
        readings = check.serve_gaps(
            self.params, self.module, self.config, self.dims, finished,
            layers, **kwargs)
        numbers = check.gap_numbers(readings["gaps"],
                                    self.section["tail_from"])
        if layers:
            numbers.update(check.routing_numbers(
                readings, self.section["slack_from"]))
        return numbers, readings

    def judge(self, numbers):
        return check.judge(numbers, self.section["limits"])


@pytest.fixture(scope="module")
def standin():
    return Standin()


@pytest.fixture(scope="module")
def sound(standin):
    finished = standin.serve()
    return finished, standin.read(finished)


def test_unforced_the_gap_is_blind_for_a_sound_routed_program(
        standin, sound):
    """(a) The guard against taking the forcing out: the reference on
    its OWN choices reads a sound program's gap_tail_mean above 100
    times what it reads on the program's (and over the limit)."""
    finished, (forced, _readings) = sound
    unforced, readings = standin.read(finished, layers=())
    assert "slack" not in readings
    assert unforced["gap_tail_mean"] > 100 * max(
        forced["gap_tail_mean"], 1e-5)
    assert unforced["gap_tail_mean"] > \
        standin.section["limits"]["gap_tail_mean"]


def test_forced_the_sound_program_holds_both_limits(standin, sound):
    """(b)"""
    finished, (numbers, readings) = sound
    ok, lines = standin.judge(numbers)
    assert ok, lines
    assert len(readings["gaps"]) == REQUESTS * NEW
    gaps = np.asarray(readings["gaps"])
    assert (gaps == 0).mean() > 0.95
    assert numbers["routing_rejected_share"] == 0.0
    # every position of every layer was judged, and flips there are
    positions = REQUESTS * (PROMPT + NEW - 1)
    assert len(readings["slack"]) == positions * len(standin.layers)
    assert readings["positions_unrecorded"] == 0
    assert 0.01 < numbers["routing_flip_share"] < 0.2
    assert 0 < numbers["slack_max"] < standin.section["slack_from"]


def test_another_weighting_equation_fails_the_gap_under_forcing(
        standin):
    """(c) The chosen experts weighed by a softmax of the router's
    outputs, where the reference normalises their sigmoids."""
    numbers, _readings = standin.read(standin.serve(weigh="softmax"))
    ok, lines = check.judge(
        numbers, {"gap_tail_mean":
                  standin.section["limits"]["gap_tail_mean"]})
    assert not ok, lines


def test_a_program_that_reroutes_fails_the_admission_not_the_gap(
        standin):
    """(d) One position in a hundred sends its last expert to a random
    one and computes with it: the reference follows it, so the gap
    cannot see it, and the slack does. Both numbers are needed."""
    numbers, _readings = standin.read(
        standin.serve(reroute_share=0.01))
    limits = standin.section["limits"]
    assert numbers["routing_rejected_share"] > \
        limits["routing_rejected_share"]
    assert numbers["gap_tail_mean"] <= limits["gap_tail_mean"]
    assert not standin.judge(numbers)[0]


@pytest.mark.parametrize("which", (0, 1))
def test_the_configurations_controls_come_out_not_correct(
        standin, sound, which):
    """The stand-in's ``check.control``: its lower-precision path (the
    convolution state in int4) through program_model, and the record
    of its choices corrupted before the check."""
    control = check.controls(standin.section["control"])[which]
    numbers, _readings = standin.read(standin.serve(**control))
    ok, lines = standin.judge(numbers)
    assert not ok, lines
    if "decisions" in control:
        assert "routing_rejected_share" in \
            [l.split()[1].rstrip(":") for l in lines if "FAILED" in l]
        assert numbers["slack_max"] == np.inf or \
            numbers["slack_max"] > 0.1


def test_a_position_without_a_record_is_counted_not_guessed(
        standin, sound):
    """(e) A request whose record starts at position 5 (a prefix
    served from shared pages): the reference takes its own choice at
    the five, says so, and judges the rest."""
    finished, (_numbers, whole) = sound
    request = dict(finished[0])
    record = request["decisions"]
    request["decisions"] = {"first": 5, "layers": {
        name: rows[5:] for name, rows in record["layers"].items()}}
    numbers, readings = standin.read([request])
    positions = PROMPT + NEW - 1
    assert readings["positions"] == positions
    assert readings["positions_unrecorded"] == 5
    assert len(readings["slack"]) == \
        (positions - 5) * len(standin.layers)
    assert readings["requests_without_record"] == 0
    assert numbers["routing_rejected_share"] is not None


@pytest.mark.parametrize("fault", (
    "none", "not_a_dict", "no_layer", "wrong_width", "floats",
    "first_past_the_end"))
def test_declared_decisions_and_no_record_is_not_correct(
        standin, sound, fault):
    """(f) An engine that gives no record (or one of another shape)
    for a finished request of a module that declares decisions."""
    finished, _read = sound
    request = dict(finished[0])
    layers = dict(request["decisions"]["layers"])
    name = standin.layers[0][0]
    request["decisions"] = {
        "none": None, "not_a_dict": [1, 2],
        "no_layer": {"first": 0, "layers": {
            k: v for k, v in layers.items() if k != name}},
        "wrong_width": {"first": 0, "layers": dict(
            layers, **{name: layers[name][:, :3]})},
        "floats": {"first": 0, "layers": dict(
            layers, **{name: layers[name].astype(np.float32)})},
        "first_past_the_end": {"first": 10_000, "layers": layers},
    }[fault]
    numbers, readings = standin.read([request, finished[1]])
    assert readings["requests_without_record"] == 1
    assert numbers["routing_rejected_share"] is None
    ok, lines = standin.judge(numbers)
    assert not ok
    assert any("routing_rejected_share: None" in l and "FAILED" in l
               for l in lines)


def test_an_index_no_top_k_could_give_is_rejected_outright(
        standin, sound):
    """The same expert twice in a row, or one out of range, would be
    weighed as handed and could read a slack of 0: refused instead."""
    finished, _read = sound
    request = dict(finished[0])
    name, _k, n = standin.layers[2]
    rows = np.array(request["decisions"]["layers"][name])
    rows[7] = rows[7, 0]          # four times the best expert
    rows[9, 1] = n                # out of range
    request["decisions"] = {"first": 0, "layers": dict(
        request["decisions"]["layers"], **{name: rows})}
    numbers, readings = standin.read([request])
    assert sum(1 for s in readings["slack"] if s == np.inf) == 2
    assert numbers["slack_max"] == np.inf


def test_the_bound_on_served_tokens_takes_the_same_requests(standin,
                                                            sound):
    """(g) The longest request, then the others in an order drawn
    from the seed, until the bound is reached; the same on every
    call, another choice for another seed."""
    finished, _read = sound
    finished = [dict(r, tokens=r["tokens"][:NEW - 5 * (r["idx"] % 3)])
                for r in finished]
    bound = standin.section["served_tokens_at_most"]
    taken = [r["idx"] for r in check.sample(finished, bound, SEED)]
    assert taken == [r["idx"] for r in
                     check.sample(list(reversed(finished)), bound, SEED)]
    longest = min(finished, key=check._longest_first)["idx"]
    assert longest in taken
    counts = {r["idx"]: len(r["tokens"]) for r in finished}
    total = sum(counts[i] for i in taken)
    assert bound <= total < bound + NEW and len(taken) < len(finished)
    assert taken != [r["idx"] for r in
                     check.sample(finished, bound, SEED + 1)]
    assert len(check.sample(finished, None, SEED)) == len(finished)
    # ... and serve_gaps reads those and says how many it left out
    _numbers, readings = standin.read(
        finished, served_tokens_at_most=bound, seed=SEED)
    assert sorted(set(readings["request"])) == sorted(taken)
    assert (readings["requests"], readings["requests_finished"]) == \
        (len(taken), len(finished))


def test_the_references_slack_by_hand(standin):
    """One position: the handed set swaps the reference's 4th for its
    6th; the slack is the difference of those two selection scores,
    and 0 for the reference's own set in any order."""
    plain = spec.load_module(spec.ROOT, BENCH,
                             "reference/routed_standin_plain.py")
    w = jax.tree_util.tree_map(
        lambda leaf: leaf.astype(jnp.float32),
        standin.params["layer_0"])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 128))
    select = jax.nn.sigmoid(h @ w["router"]["kernel"]) \
        + w["router"]["bias"]
    order = np.argsort(-np.asarray(select), axis=-1)
    handed = np.stack([order[0, [3, 1, 0, 2]],
                       order[1, [0, 1, 2, 5]]]).astype(np.int32)
    out, slack = plain.routed_experts(h, w, jnp.asarray(handed), 4)
    own, own_slack = plain.routed_experts(
        h, w, jnp.full((2, 4), -1, jnp.int32), 4)
    assert float(slack[0]) == 0.0 and np.all(np.asarray(own_slack) == 0)
    want = float(select[1, order[1, 3]] - select[1, order[1, 5]])
    assert want > 0 and float(slack[1]) == pytest.approx(want, rel=1e-5)
    np.testing.assert_allclose(out[0], own[0], rtol=1e-5, atol=1e-6)
    assert not np.allclose(out[1], own[1], atol=1e-4)


def _serve_gaps_of_pr_26(params, model_module, config, dims, finished):
    """benchmark/check.py::serve_gaps as PR 26 left it, word for word:
    what a module without decisions was judged by."""
    out = {"gaps": [], "best": [], "request": []}
    # longest first: the few large programs compile (or load) first
    for request in sorted(finished, key=lambda r: (
            -len(r["prompt"]) - len(r["tokens"]), r["idx"])):
        prompt, served = request["prompt"], request["tokens"]
        n = len(served)
        sequence = prompt + served[:-1]
        padded = check._pad(len(sequence), check.SEQ_BUCKET)
        tokens = jnp.asarray(
            sequence + [0] * (padded - len(sequence)), jnp.int32)
        first = len(prompt) - 1
        rows = list(range(first, first + n))
        rows += [rows[-1]] * (check._pad(n, check.ROW_BUCKET) - n)
        picked = jnp.asarray(served + [served[-1]] * (len(rows) - n),
                             jnp.int32)
        logits = model_module.teacher_forced_logits(
            params, tokens, jnp.asarray(rows, jnp.int32), config, dims)
        gaps, best = check._row_readings(logits, picked)
        out["gaps"].extend(np.asarray(gaps)[:n].tolist())
        out["best"].extend(np.asarray(best)[:n].tolist())
        out["request"].extend([request["idx"]] * n)
    out["requests"] = len(finished)
    return out


def test_a_module_without_decisions_is_judged_byte_for_byte_as_before():
    """(h) dense_mha through the new serve_gaps against the old
    function kept above: the same readings to the last bit, in the
    same order, and the same numbers."""
    base = spec.load_config(BENCH["configs"][0]["name"])
    model = harness.merged(base, True)
    module = spec.load_model(model)
    assert spec.decision_layers(module, model, module.dims(model)) == []
    dims = module.dims(model)
    params = weights.make_params(module.param_leaves(dims), 5,
                                 jnp.bfloat16)
    rng = np.random.default_rng(5)
    finished = [{"idx": i,
                 "prompt": rng.integers(1, dims["vocab"],
                                        int(rng.integers(8, 60))).tolist(),
                 "tokens": rng.integers(1, dims["vocab"],
                                        int(rng.integers(4, 40))).tolist()}
                for i in range(12)]
    old = _serve_gaps_of_pr_26(params, module, model, dims, finished)
    new = check.serve_gaps(params, module, model, dims, finished,
                           spec.decision_layers(module, model, dims),
                           model["check"].get("served_tokens_at_most"),
                           seed=5)
    assert new.pop("requests_finished") == len(finished)
    assert json.dumps(new, sort_keys=True) == \
        json.dumps(old, sort_keys=True)
    assert list(new) == list(old)
    tail_from = model["check"]["tail_from"]
    assert check.gap_numbers(new["gaps"], tail_from) == \
        check.gap_numbers(old["gaps"], tail_from)
    assert set(check.gap_numbers(new["gaps"], tail_from)) == \
        {"gap_tail_mean", "gap_max", "gap_mean"}

"""A stack of stateful, attention and routed-expert blocks, one mixer
a block, through ContinuousBatcher: the paged pool with fewer K/V
heads than query heads, a fixed-size state per slot beside it, experts
with no dropped token and a record of their choices, all as one chip's
share of a deployment. Every case that is about HAVING A PER-SLOT
STATE runs for both stateful kinds (STACKS): the state-space stack
(``ssm``: Mamba-2, relu^2 experts) and the delta-rule stack
(``delta``: KDA, gated attention, gated experts). CPU, tiny sizes,
seeded random weights, float32 on both sides so that a tolerance is
rounding alone; LOGITS are compared, not tokens: every served token's
logit has to lie within a tolerance of the plain reference's best at
its position (benchmark/reference/hybrid_*_plain.py, float32
"highest", sequential recurrence, no cache), the reference run on the
engine's own expert choices, each of which has to be (within a
tolerance) one the reference would have made."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.models import inference as inf
from batch_shipyard_tpu.models import delta, moe, serving, ssm
from batch_shipyard_tpu.models import transformer as tfm
from batch_shipyard_tpu.models.serving import Request
from benchmark import spec, weights
from benchmark.reference import hybrid_delta_moe_plain, hybrid_ssm_moe_plain

# The tiny sizes: d 64, 4 query / 2 KV heads of 16, 8 experts top-2 of
# which 4 held, vocabulary 512 of which 256 held; 4 SSM heads of 16,
# 2 groups, state 16, pattern MEM*EME ...
FILE = dict(
    hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, mamba_num_heads=4, mamba_head_dim=16,
    n_groups=2, ssm_state_size=16, conv_kernel=4, chunk_size=8,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
    n_routed_experts=4, num_experts_per_tok=2, num_hidden_layers=7,
    hybrid_override_pattern="MEM*EME", vocab_size=256,
    layer_norm_epsilon=1e-5, routed_scaling_factor=2.5,
    model_module="hybrid_ssm_moe",
    share={"first_expert": 0, "experts_of": 8})
# ... or one period of the delta stack: attention then three delta
# layers of 4 heads of 16, each followed by its experts (8 blocks).
DELTA_FILE = dict(
    hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, linear_attn_config={
        "short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
        "num_kv_heads": None},
    sizes_set={"kda_gate_rank": 16, "kda_chunk": 8},
    moe_intermediate_size=32, n_shared_experts=1, n_routed_experts=4,
    num_experts_per_tok=2, num_hidden_layers=4, gqa_layers=[0],
    first_k_dense_replace=0, vocab_size=256, rms_norm_eps=1e-5,
    routed_scaling_factor=1, use_rope=False, use_gqa_gate=True,
    tie_word_embeddings=False, model_module="hybrid_delta_moe",
    share={"first_expert": 0, "experts_of": 8})
ENGINE = {"num_slots": 4, "max_decode_len": 128}
PAGE = 16
# float32 program against the float32 reference: the two differ by
# the order of their sums (a chunked scan against a recurrence, a
# matmul over all held experts against one expert after another),
# some 1e-5 of a logit of size 1 to 4 after seven blocks; 1e-3 leaves
# two orders of room and is a
# thirtieth of what bfloat16 alone explains (check.tail_from 0.03).
GAP = 1e-3
# ... and a selection score (a sigmoid plus 0) by some 1e-6.
SLACK = 1e-4

# A stateful kind: its mixer's name in the tree, the cache leaves the
# mixer's module declares (state first, then tail), its file and
# reference.
STACKS = {
    "ssm": (FILE, hybrid_ssm_moe_plain, ssm.STATE_LEAVES),
    "delta": (DELTA_FILE, hybrid_delta_moe_plain, delta.STATE_LEAVES)}


@dataclasses.dataclass
class Share:
    """One stack at its tiny size: unpacks as (file, dims, the
    program's config, seeded float32 weights)."""
    mixer: str
    file: dict
    dims: dict
    config: tfm.TransformerConfig
    params: dict
    module: object
    plain: object
    leaves: tuple

    def __iter__(self):
        return iter((self.file, self.dims, self.config, self.params))

    def layers(self, kind: str) -> list:
        return [f"layer_{i}" for i, k in enumerate(
            tfm.layer_kinds(self.config)) if k == kind]

    @property
    def tail_channels(self) -> int:
        return self.dims["conv_dim"] if self.mixer == "ssm" \
            else 3 * self.dims["d_inner"]

    def kept_in(self, dtype) -> tfm.TransformerConfig:
        """The config with the mixer's state kept in ``dtype``."""
        sizes = getattr(self.config, self.mixer)
        return dataclasses.replace(self.config, **{
            self.mixer: dataclasses.replace(sizes, state_dtype=dtype)})


@pytest.fixture(scope="module", params=sorted(STACKS))
def share(request):
    file, plain, leaves = STACKS[request.param]
    module = spec.load_model(file)
    dims = module.dims(file)
    config = dataclasses.replace(
        module.program_model(file, dims, ENGINE), dtype=jnp.float32,
        param_dtype=jnp.float32)
    params = weights.make_params(module.param_leaves(dims), 11,
                                 jnp.float32)
    # a routing bias that matters: selection by score + bias, weights
    # by the score alone
    for name, _k, _n in module.decision_layers(file, dims):
        params[name]["experts"]["e_score_correction_bias"] = \
            0.05 * jax.random.normal(jax.random.PRNGKey(int(name[6:])),
                                     (8,))
    return Share(request.param, file, dims, config, params, module,
                 plain, leaves)


def _engine(config, params, **kwargs):
    kwargs.setdefault("num_slots", ENGINE["num_slots"])
    return serving.ContinuousBatcher(
        config, params, max_decode_len=ENGINE["max_decode_len"],
        kv_page_size=PAGE, **kwargs)


def _prompts(count, low=5, high=60, seed=0):
    rng = np.random.default_rng(seed)
    return {f"r{i}": [int(t) for t in rng.integers(
        1, FILE["vocab_size"], rng.integers(low, high))]
        for i in range(count)}


def _serve(engine, prompts, new_tokens):
    for request_id, prompt in prompts.items():
        engine.submit(Request(request_id, prompt,
                              max_new_tokens=new_tokens[request_id]))
    done = {}
    while engine.pending():
        for request_id, tokens in engine.step():
            done[request_id] = tokens
    return done


def _judged(share, prompt, served, record):
    """(each served token's gap below the reference's best logit, the
    slack of every recorded choice) with the reference run on the
    record's choices."""
    file, dims, _config, params = share
    sequence = prompt + served[:-1]
    # padded at its end to a whole number of 64 (a row of -1: the
    # reference's own choice): everything is causal, so no position
    # that is read sees the padding, and the reference compiles once a
    # bucket and not once a length
    length = len(sequence)
    fill = -length % 64
    handed = {name: jnp.pad(jnp.asarray(rows)[:length],
                            ((0, fill), (0, 0)), constant_values=-1)
              for name, rows in record["layers"].items()}
    logits, slacks = share.module.teacher_forced_logits(
        params, jnp.asarray(sequence + [0] * fill, jnp.int32),
        jnp.arange(len(prompt) - 1, length), file, dims,
        decisions=handed)
    slacks = {name: slack[:length] for name, slack in slacks.items()}
    best = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, jnp.asarray(served)[:, None],
                             axis=-1)[:, 0]
    return np.asarray(best - at), np.concatenate(
        [np.asarray(slack) for slack in slacks.values()])


@pytest.fixture(scope="module")
def served(share):
    """Ten requests of different lengths through four slots: they
    share decode steps, wait for slots and reuse them."""
    _file, _dims, config, params = share
    engine = _engine(config, params)
    prompts = _prompts(10)
    rng = np.random.default_rng(1)
    new_tokens = {r: int(rng.integers(3, 14)) for r in prompts}
    done = _serve(engine, prompts, new_tokens)
    records = {r: engine.take_decisions(r) for r in done}
    return engine, prompts, new_tokens, done, records


# ------------------- (a) the engine against the reference


def test_prefill_then_decode_agree_with_the_references_full_pass(
        share, served):
    _engine_, prompts, new_tokens, done, records = served
    assert set(done) == set(prompts)
    for request_id, tokens in done.items():
        assert len(tokens) == new_tokens[request_id]
        gaps, slack = _judged(share, prompts[request_id], tokens,
                              records[request_id])
        assert gaps.max() < GAP, (request_id, gaps.max())
        assert slack.max() < SLACK, (request_id, slack.max())


def test_the_reference_alone_picks_the_same_tokens(share, served):
    """Without the record the reference makes its own choices: at
    float32 they are the engine's, and so are the tokens."""
    file, dims, _config, params = share
    _engine_, prompts, _new, done, _records = served
    request_id = max(done, key=lambda r: len(prompts[r]))
    sequence = prompts[request_id] + done[request_id][:-1]
    logits = share.module.teacher_forced_logits(
        params, jnp.asarray(sequence, jnp.int32),
        jnp.arange(len(prompts[request_id]) - 1, len(sequence)),
        file, dims)
    assert [int(t) for t in jnp.argmax(logits, -1)] == done[request_id]


# ------------------- (b) the shares add up


def test_the_two_halves_experts_and_one_shared_expert_are_the_block(
        share):
    """Experts 0-3 and experts 4-7 of the router's 8, each half's
    routed part, plus ONE shared expert, are what the uncut reference
    gives for the whole E block; in the program and in the reference.
    1e-5: float32 sums in another order."""
    _file, dims, _config, params = share
    plain, scale = share.plain, dims["scale"]
    d = dims["d_model"]
    half = params[share.layers("experts")[0]]["experts"]
    key = jax.random.PRNGKey(5)
    other = {name: jax.random.normal(
        jax.random.fold_in(key, i), stack.shape) / np.sqrt(
            stack.shape[1])
        for i, (name, stack) in enumerate(sorted(half.items()))
        if name.startswith("experts_")}
    uncut = dict(half, **{name: jnp.concatenate([half[name], stack])
                          for name, stack in other.items()})
    h = jax.random.normal(jax.random.PRNGKey(6), (24, d))
    own = jnp.full((24, 2), -1, jnp.int32)
    sizes = {"top_k": 2, "scale": scale}
    whole, _slack = plain.experts(h, uncut, own, first=0, **sizes)
    # the shared expert alone: the block with no expert held
    shared, _slack = plain.experts(
        h, dict(half, **{name: stack[:0] for name, stack in
                         other.items()}), own, first=0, **sizes)
    # the reference's halves
    use, weigh, _ = plain.route(h, uncut, own, 2, scale)
    parts = [plain.routed_part(h, dict(half, **tree), use, weigh, first)
             for first, tree in ((0, {}), (4, other))]
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole,
                               atol=1e-5)
    assert float(jnp.abs(parts[0]).max()) > 0.1 < float(
        jnp.abs(parts[1]).max())
    # the program's halves: each layer adds the shared expert, so one
    # of the two is taken out again
    outs = []
    for first, tree in ((0, {}), (4, other)):
        layer = moe.RoutedExperts(
            moe.RoutedConfig(d_model=d, n_experts=8, top_k=2,
                             d_expert=32, d_shared=dims["d_shared"],
                             scale=scale, experts_held=4,
                             first_expert=first,
                             gated="experts_gate" in half),
            dtype=jnp.float32)
        out, sown = layer.apply({"params": dict(half, **tree)},
                                h[None], mutable=["decisions"])
        outs.append(out[0])
        np.testing.assert_array_equal(
            np.sort(sown["decisions"]["chosen"][0][0]), np.sort(use))
    np.testing.assert_allclose(outs[0] + outs[1] - shared, whole,
                               atol=1e-5)


def test_the_two_vocabulary_halves_logits_concatenate(share):
    """Rows 0-255 and rows 256-511 of an uncut lm_head: the program's
    head over each half gives the uncut reference's logits side by
    side (a matmul's columns are independent)."""
    _file, dims, config, params = share
    plain = share.plain
    d = dims["d_model"]
    uncut = jax.random.normal(jax.random.PRNGKey(8), (d, 512)) / 8.0
    hidden = jax.random.normal(jax.random.PRNGKey(9), (5, d))
    normed = plain.rmsnorm(hidden, params["final_norm"]["scale"], 1e-5)
    halves = [tfm.output_logits(
        config, {"lm_head": {"kernel": uncut[:, lo:lo + 256]}}, normed)
        for lo in (0, 256)]
    np.testing.assert_allclose(
        jnp.concatenate(halves, axis=-1),
        plain.head_logits(hidden, params["final_norm"], uncut, 1e-5),
        atol=1e-5)


# ------------------- (c) a prompt padded to a longer bucket


@pytest.mark.parametrize("chunk", (None, 8))
def test_bucket_padding_advances_neither_state_nor_tail(share, chunk):
    """Eleven tokens in a bucket of 32 (prompt_len dynamic) leave the
    state of position 10, the convolution's tail of positions 8-10 and
    the first token's logits that the same eleven tokens leave with no
    padding at all; a prefill in chunks of 8 (the last two all
    padding) too. 1e-5: the chunked scan's sums fall otherwise."""
    _file, _dims, config, params = share
    model = tfm.TransformerLM(inf.decode_config(config, 128))
    prompt = _prompts(1, 11, 12)["r0"]
    assert len(prompt) == 11
    # (each a compiled program, the prompt's length traced as the
    # engine's prefill programs trace it)
    def prefill(chunk, tokens, length):
        return jax.jit(functools.partial(
            serving._dense_prefill, model, chunk))(
                params, jnp.asarray([tokens]), length)

    exact, last, _chosen = prefill(None, prompt, 11)
    padded, last_padded, _chosen = prefill(chunk, prompt + [0] * 21, 11)
    np.testing.assert_allclose(last_padded, last, atol=1e-5)
    assert int(jnp.argmax(last_padded)) == int(jnp.argmax(last))
    mixer, (state, _tail) = share.mixer, share.leaves
    assert set(share.leaves) <= set(inf.SLOT_STATE_LEAVES)
    states = 0
    for name in share.layers(mixer):
        for leaf in share.leaves:
            states += 1
            np.testing.assert_allclose(
                padded[name][mixer][leaf], exact[name][mixer][leaf],
                atol=1e-5, err_msg=f"{name} {leaf}")
    assert states == 6
    # ... and padding that DID advance it would show
    wrong, _last, _chosen = prefill(None, prompt + [0] * 21, 32)
    first = share.layers(mixer)[0]
    assert float(jnp.abs(wrong[first][mixer][state]
                         - exact[first][mixer][state]).max()) > 1e-2


# ------------------- (d) a slot reused, with a step in flight


def test_a_reused_slot_serves_what_a_fresh_engine_serves(share):
    """Two slots, seven requests, some ending on an eos_id (so that
    the step in flight computes one overshoot token and writes a state
    nobody may read): every request gets the tokens a fresh engine
    gives it alone, and its logits hold against the reference."""
    _file, _dims, config, params = share
    prompts = _prompts(7, seed=3)
    alone = {}
    for request_id, prompt in prompts.items():
        alone.update(_serve(_engine(config, params, num_slots=2),
                            {request_id: prompt}, {request_id: 12}))
    engine = _engine(config, params, num_slots=2)
    for i, (request_id, prompt) in enumerate(prompts.items()):
        # every other request stops early on the token it would have
        # produced fifth
        engine.submit(Request(
            request_id, prompt, max_new_tokens=12,
            eos_id=alone[request_id][4] if i % 2 else None))
    done = {}
    while engine.pending():
        for request_id, tokens in engine.step():
            done[request_id] = tokens
    assert engine.step_stats()["overshoot_tokens"] > 0
    assert engine.step_stats()["steps_overlapped"] > 0
    for i, request_id in enumerate(prompts):
        want = alone[request_id]
        if i % 2:
            want = want[:want.index(want[4]) + 1]
        assert done[request_id] == want, request_id
        gaps, slack = _judged(share, prompts[request_id],
                              done[request_id],
                              engine.take_decisions(request_id))
        assert gaps.max() < GAP and slack.max() < SLACK


# ------------------- (e) no capacity: alone or in a full step


def test_a_rows_experts_do_not_depend_on_its_batch_mates(share):
    """dense_experts on 96 rows and on each row alone: the same output
    to the last bits of a float32 sum (no capacity, nothing dropped),
    and what the reference's routed part gives."""
    _file, dims, _config, params = share
    w = params[share.layers("experts")[1]]["experts"]
    rows = jax.random.normal(jax.random.PRNGKey(2), (96, dims["d_model"]))
    chosen, weigh = moe.route_sigmoid(
        rows @ w["router_kernel"], w["e_score_correction_bias"], 2,
        dims["scale"])
    stacks = (w["experts_up"], w["experts_down"], 0,
              w.get("experts_gate"))
    full = moe.dense_experts(rows, chosen, weigh, *stacks)
    for i in (0, 17, 95):
        one = moe.dense_experts(rows[i:i + 1], chosen[i:i + 1],
                                weigh[i:i + 1], *stacks)
        np.testing.assert_allclose(one[0], full[i], rtol=0, atol=2e-6)
    np.testing.assert_allclose(
        full, share.plain.routed_part(rows, w, chosen, weigh, 0),
        atol=1e-5)
    # every pair on a held expert was computed: none dropped
    held = (chosen < 4).sum(axis=1)
    assert float(jnp.abs(full[held == 0]).max()) == 0.0
    assert float(jnp.abs(full[held > 0]).min(axis=0).max()) > 0.0


def test_a_request_is_served_the_same_alone_and_in_a_full_step(
        share, served):
    _file, _dims, config, params = share
    _engine_, prompts, new_tokens, done, _records = served
    for request_id in ("r0", "r7"):
        alone = _serve(_engine(config, params),
                       {request_id: prompts[request_id]}, new_tokens)
        assert alone[request_id] == done[request_id]


# ------------------- (f) the record of choices


def test_take_decisions_covers_every_position_once(share, served):
    engine, prompts, _new, done, records = served
    for request_id, record in records.items():
        positions = len(prompts[request_id]) + len(done[request_id]) - 1
        assert record["first"] == 0
        assert list(record["layers"]) == share.layers("experts")
        for rows in record["layers"].values():
            assert rows.shape == (positions, 2)
            assert rows.dtype == np.int32
            assert rows.min() >= 0 and rows.max() < 8     # of ALL 8
            assert (rows[:, 0] != rows[:, 1]).all()
        # handed over once
        assert engine.take_decisions(request_id) is None
    assert engine.take_decisions("nobody") is None
    # experts held elsewhere are chosen too, and recorded
    assert max(rows.max() for record in records.values()
               for rows in record["layers"].values()) >= 4


def test_a_cancelled_request_leaves_no_record(share):
    _file, _dims, config, params = share
    engine = _engine(config, params)
    engine.submit(Request("gone", _prompts(1)["r0"], max_new_tokens=9))
    engine.step()
    engine.step()
    assert engine.cancel("gone")
    while engine.pending():
        engine.step()
    assert engine.take_decisions("gone") is None
    assert not engine._decisions and not engine._decisions_done


# ------------------- (g) prefills behind the step in flight


def _behind_a_step(share, sampling, late, prepare=None):
    """One request decoding, then ``late`` more submitted before ONE
    call, a slot for each. -> (engine, served, records)."""
    _file, _dims, config, params = share
    engine = _engine(config, params, seed=3, sampling=sampling)
    if prepare is not None:
        prepare(engine)
    (first, prompt), *others = _prompts(1 + late, seed=5).items()
    engine.submit(Request(first, prompt, max_new_tokens=11))
    engine.step()
    engine.step()
    for n, (request_id, prompt) in enumerate(others):
        engine.submit(Request(request_id, prompt,
                              max_new_tokens=4 + 3 * n))
    done = {}
    while engine.pending():
        for request_id, tokens in engine.step():
            done[request_id] = tokens
    return engine, done, {r: engine.take_decisions(r) for r in done}


@pytest.mark.parametrize("late", [2, 3])
@pytest.mark.parametrize("sampling", ["greedy", "sampled"])
def test_prefills_behind_a_step_serve_the_serial_orders_tokens(
        share, sampling, late, serialised):
    """A stateful, routed engine: two or three prefills dispatched in
    one call behind the decode step in flight, their first tokens
    seated on the device, serve token for token what the serial order
    serves, sampled streams too (the key is split in the same order),
    and take_decisions hands over the same record, the prefill's
    rows among it."""
    config = inf.SamplingConfig() if sampling == "greedy" else \
        inf.SamplingConfig(temperature=0.8, top_k=12)
    engine, done, records = _behind_a_step(share, config, late)
    serial, want, want_records = _behind_a_step(share, config, late,
                                                serialised)
    stats = engine.step_stats()
    assert (stats["prefills"], stats["prefills_overlapped"]) == (
        1 + late, late)
    assert stats["steps_overlapped"] == stats["decode_steps"] - 1
    assert sum(stats["settles"].values()) == 1 == \
        stats["settles"]["idle"]
    assert serial.step_stats()["steps_overlapped"] == 0
    assert done == want and len(done) == 1 + late
    prompts = _prompts(1 + late, seed=5)
    for request_id, record in records.items():
        twin = want_records[request_id]
        assert record["first"] == twin["first"] == 0
        assert list(record["layers"]) == share.layers("experts")
        positions = len(prompts[request_id]) + len(done[request_id]) - 1
        for name, rows in record["layers"].items():
            assert rows.shape == (positions, 2)
            np.testing.assert_array_equal(rows, twin["layers"][name])
        if sampling == "greedy":
            gaps, slack = _judged(share, prompts[request_id],
                                  done[request_id], record)
            assert gaps.max() < GAP and slack.max() < SLACK


def test_a_first_token_that_ends_its_request_hands_over_its_record(
        share):
    """max_new_tokens 1 beside a request in flight: the prefill's
    choices are on record when the first token lands and finishes the
    request, the decode step behind it never seats the slot, and the
    expert counters count landed decode steps only."""
    _file, _dims, config, params = share
    engine = _engine(config, params)
    prompts = _prompts(2, seed=9)
    engine.submit(Request("r0", prompts["r0"], max_new_tokens=8))
    engine.step()
    engine.step()
    pairs = engine.step_stats()["expert_pairs_chosen"]
    engine.submit(Request("r1", prompts["r1"], max_new_tokens=1))
    (finished,) = engine.step()
    assert finished[0] == "r1" and len(finished[1]) == 1
    record = engine.take_decisions("r1")
    assert record["first"] == 0
    for rows in record["layers"].values():
        assert rows.shape == (len(prompts["r1"]), 2)
    # the call landed ONE decode step, with r0 alone in it
    layers = len(share.layers("experts"))
    assert engine.step_stats()["expert_pairs_chosen"] == \
        pairs + 2 * layers
    alone = _serve(_engine(config, params), {"r1": prompts["r1"]},
                   {"r1": 1})
    assert alone["r1"] == finished[1]
    while engine.pending():
        engine.step()
    assert engine.step_stats()["overshoot_tokens"] == 0


# ------------------- the state per slot, the pool, the counters


def test_the_cache_holds_a_state_per_slot_beside_the_paged_kv(share,
                                                             served):
    engine = served[0]
    assert engine.stateful and engine.paged
    state, tail = share.leaves
    layer = engine.cache[share.layers(share.mixer)[0]][share.mixer]
    assert set(layer) == {state, tail}      # a slot row, no cursor
    assert layer[state].shape == (4, 4, 16, 16)
    assert layer[state].dtype == jnp.float32
    assert layer[tail].shape == (4, 3, share.tail_channels)
    # two K/V heads of 16 in the pool's rows, not the four query heads
    pool = engine.cache[share.layers("attn")[0]]["attn"]["k_pages"]
    assert pool.shape[1:] == (PAGE, 2 * 16)
    per_slot = 3 * (4 * 16 * 16 * 4 + 3 * share.tail_channels * 4)
    assert inf.slot_state_bytes(engine.cache) == per_slot
    idle = engine.occupancy()
    assert idle["state_slots_in_use"] == 0
    assert idle["experts_held"] == len(share.layers("experts")) * 4
    engine.submit(Request("x", [1, 2, 3], max_new_tokens=4))
    engine.step()
    busy = engine.occupancy()
    assert busy["state_slots_in_use"] == 1
    assert busy["state_bytes_held"] == per_slot
    while engine.pending():
        engine.step()


@pytest.mark.parametrize("mixer", sorted(STACKS))
def test_idle_slots_are_parked_and_their_state_is_left_alone(mixer):
    """_park_idle_cursors touches cursors alone: a state leaf has
    none."""
    state, tail = STACKS[mixer][2]
    cache = {"layer_0": {mixer: {state: jnp.ones((3, 2, 2, 2)),
                                 tail: jnp.ones((3, 3, 4))}},
             "layer_1": {"attn": {"length": jnp.asarray([5, 6, 7])}}}
    parked = inf._park_idle_cursors(
        cache, jnp.asarray([True, False, True]))
    assert parked["layer_1"]["attn"]["length"].tolist() == [5, 0, 7]
    assert float(parked["layer_0"][mixer][state].min()) == 1.0
    assert inf.slot_state_bytes(cache) == (2 * 2 * 2 + 3 * 4) * 4


def test_matched_pages_are_shared_and_the_whole_prompt_is_run(share):
    """A model with a per-slot state never seats a K/V page for a
    position whose state it does not hold: a request that matches
    pages of the index shares them (one copy in the pool) and still
    prefills its whole prompt, the matched pages' rows going to the
    scratch page. Its tokens are those of an engine without the
    index, and its record covers the prompt from position 0."""
    _file, _dims, config, params = share
    prefix = _prompts(1, 40, 41, seed=5)["r0"]
    prompts = {f"s{i}": prefix + tail for i, tail in enumerate(
        _prompts(3, 5, 20, seed=6).values())}
    new_tokens = dict.fromkeys(prompts, 6)
    shared = _engine(config, params)
    done = {}
    for request_id, prompt in prompts.items():   # one after another
        done.update(_serve(shared, {request_id: prompt}, new_tokens))
    stats = shared.prefix_stats()
    assert stats["hit_pages"] == 2 * (40 // PAGE)
    plain_engine = _engine(config, params, prefix_cache=False)
    assert _serve(plain_engine, prompts, new_tokens) == done
    for request_id in prompts:
        record = shared.take_decisions(request_id)
        assert record["first"] == 0
        assert len(record["layers"][share.layers("experts")[0]]) == \
            len(prompts[request_id]) + 5


def test_the_expert_counters_count_decode_steps_pairs(share):
    _file, _dims, config, params = share
    engine = _engine(config, params)
    _serve(engine, _prompts(4), dict.fromkeys(_prompts(4), 6))
    stats = engine.step_stats()
    routed = len(share.layers("experts"))
    # 4 requests x 5 decoded tokens x the routed layers x top-2
    assert stats["expert_pairs_chosen"] == 4 * 5 * routed * 2
    assert 0 < stats["expert_pairs_here"] < stats["expert_pairs_chosen"]
    assert 0 < stats["experts_hit"] <= stats["decode_steps"] * routed * 4


def test_step_rows_carry_the_new_counters(share, recorder):
    from batch_shipyard_tpu.trace import spans as trace_spans
    _file, _dims, config, params = share
    engine = _engine(config, params)
    _serve(engine, _prompts(3), dict.fromkeys(_prompts(3), 5))
    rows = [row["attrs"] for row in recorder()
            if row["kind"] == trace_spans.SPAN_SERVE_STEP]
    assert rows
    for row in rows:
        assert {"expert_pairs_here", "expert_pairs_chosen",
                "experts_hit", "state_slots_in_use",
                "state_bytes_held", "experts_held"} <= set(row)
    assert sum(row["expert_pairs_chosen"] for row in rows) == \
        engine.step_stats()["expert_pairs_chosen"]
    landed = [row for row in rows if row["expert_pairs_chosen"]]
    assert all(row["experts_hit"] <= 4 * len(share.layers("experts"))
               for row in landed)


def test_each_block_kind_has_its_own_scope_in_the_step_program(share):
    """layer_i/<kind>/... in the lowered decode step's operation
    names, so that a device trace can be split by kind."""
    _file, _dims, config, params = share
    engine = _engine(config, params)
    text = serving._decode_step.lower(
        engine.model, engine.sampling, params, engine.cache,
        engine._tokens, engine._positions, engine._active,
        engine._key).as_text(debug_info=True)
    for kind in (share.mixer, "experts", "attn"):
        for layer in share.layers(kind):
            assert f"{layer}/{kind}" in text, (layer, kind)


def test_a_draft_model_is_refused_for_a_stateful_target(share):
    _file, _dims, config, params = share
    with pytest.raises(ValueError, match="per-slot state"):
        _engine(config, params, speculative=serving.SpeculativeConfig(
            draft_config=config, draft_params=params, gamma=2))


# ------------------- fewer K/V heads than query heads


def test_grouped_query_attention_is_repeated_kv_attention():
    """masked_attention, the paged xla path (impl None off the TPU,
    as for every pool) and the grouped Pallas kernel (what "kernel",
    and None on a TPU, MEAN for a grouped pool; interpret mode here)
    with 2 K/V heads under 4 query heads against the same call with
    each K/V head repeated for its group."""
    from jax.experimental.pallas import tpu as pltpu

    from batch_shipyard_tpu.ops import paged_attention as paged
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(key[0], (3, 1, 4, 16))
    pages_k = jax.random.normal(key[1], (7, 8, 2 * 16))
    pages_v = jax.random.normal(key[2], (7, 8, 2 * 16))
    table = jnp.asarray([[0, 1, 6], [2, 6, 6], [3, 4, 5]])
    lengths = jnp.asarray([11, 3, 24])
    grouped = paged.paged_decode_attention(q, pages_k, pages_v, table,
                                           lengths)
    with pltpu.force_tpu_interpret_mode():
        by_kernel = paged.paged_decode_attention(
            q, pages_k, pages_v, table, lengths, impl="kernel")

    def repeat(pool):
        return jnp.repeat(pool.reshape(7, 8, 2, 16), 2,
                          axis=2).reshape(7, 8, 4 * 16)

    want = paged.paged_decode_attention_xla(
        q, repeat(pages_k), repeat(pages_v), table, lengths)
    np.testing.assert_allclose(grouped, want, atol=1e-5)
    np.testing.assert_allclose(by_kernel, want, atol=1e-5)
    k_all = jax.random.normal(key[3], (3, 9, 2, 16))
    mask = jnp.tril(jnp.ones((9, 9), bool))[None, None, -1:, :]
    got = paged.masked_attention(q, k_all, k_all, mask, jnp.float32)
    want = paged.masked_attention(
        q, jnp.repeat(k_all, 2, axis=2), jnp.repeat(k_all, 2, axis=2),
        mask, jnp.float32)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_grouped_kernel_serves_the_gathers_tokens_and_choices(
        share, served):
    """What paged_attention_impl None means for these stacks' grouped
    pools on a TPU (PR 41), named here as "kernel" and run in interpret
    mode: the engine of ``served`` (on the CPU: the gather) serves the
    same tokens and hands over the same record of choices through the
    grouped Pallas kernel, beside a per-slot state and through slot
    reuse."""
    from jax.experimental.pallas import tpu as pltpu

    from batch_shipyard_tpu.workloads import serve
    _file, _dims, config, params = share
    _engine_, prompts, new_tokens, done, records = served
    assert config.paged_attention_impl is None
    assert serve.paged_decode_impl(config) == "xla"
    config = dataclasses.replace(config, paged_attention_impl="kernel")
    assert serve.paged_decode_impl(config) == "gqa_kernel"
    with pltpu.force_tpu_interpret_mode():
        engine = _engine(config, params)
        assert _serve(engine, prompts, new_tokens) == done
    for request_id, record in records.items():
        again = engine.take_decisions(request_id)
        assert again["first"] == record["first"]
        for name, rows in record["layers"].items():
            np.testing.assert_array_equal(again["layers"][name], rows)


@pytest.mark.parametrize("stateful", tfm.STATEFUL_KINDS)
def test_layer_kinds_from_the_list_and_from_the_stride(stateful):
    dense = tfm.TransformerConfig(n_layers=4)
    assert tfm.layer_kinds(dense) == ("dense",) * 4
    strided = dataclasses.replace(dense, moe=moe.MoEConfig(),
                                  moe_every=2)
    assert tfm.layer_kinds(strided) == ("dense", "dense_moe") * 2
    assert tfm.decision_layer_names(strided) == ()
    assert not tfm.has_slot_state(strided)
    with pytest.raises(ValueError, match="block_kinds"):
        tfm.layer_kinds(dataclasses.replace(
            dense, block_kinds=(stateful, "attn")))
    mixed = dataclasses.replace(
        dense, block_kinds=(stateful, "experts", "attn", "dense"))
    assert tfm.decision_layer_names(mixed) == ("layer_1",)
    assert tfm.paged_layer_count(mixed) == 2
    assert tfm.has_slot_state(mixed)
    assert not tfm.has_slot_state(dataclasses.replace(
        dense, block_kinds=("attn", "experts", "attn", "dense")))


def test_the_chunked_scan_is_the_recurrence():
    """ssd_scan over 37 tokens in chunks of 8 (the last one padded)
    from a given state against 37 calls of ssd_step."""
    key = jax.random.split(jax.random.PRNGKey(4), 6)
    x = jax.random.normal(key[0], (2, 37, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(key[1], (2, 37, 4)))
    a = -jnp.exp(0.3 * jax.random.normal(key[2], (4,)))
    b = jax.random.normal(key[3], (2, 37, 2, 16))
    c = jax.random.normal(key[4], (2, 37, 2, 16))
    state = jax.random.normal(key[5], (2, 4, 8, 16))
    y, last = ssm.ssd_scan(x, dt, a, b, c, state, 8)
    rows = []
    for t in range(37):
        row, state = ssm.ssd_step(x[:, t], dt[:, t], a, b[:, t],
                                  c[:, t], state)
        rows.append(row)
    np.testing.assert_allclose(y, jnp.stack(rows, 1), atol=2e-5)
    np.testing.assert_allclose(last, state, atol=2e-5)


def test_a_state_kept_in_bfloat16_is_rounded_once_a_token(share):
    """``state_dtype`` of SSMConfig and of DeltaConfig, the program's
    lower-precision switch (the benchmark's control turns it on): the
    state leaf is kept in bfloat16 and still advanced in float32. One
    token in, the kept state is the float32 one rounded once (2**-8 of
    each entry); sixty-four tokens on, a head that remembers (A_log
    -8: no decay to speak of) has gathered those roundings and reads
    beyond one rounding of its own output, while the float32 engine's
    state is untouched by the switch."""
    _file, _dims, _config, params = share
    mixer, (leaf, _tail) = share.mixer, share.leaves
    first = share.layers(mixer)[0]
    params = jax.tree_util.tree_map(lambda leaf: leaf, params)
    params[first][mixer]["A_log"] = jnp.full((4,), -8.0)
    prompt = _prompts(1, 70, 71, seed=9)["r0"]
    states = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        model = tfm.TransformerLM(
            inf.decode_config(share.kept_in(dtype), 128))
        # (each a compiled program: eagerly, every operation of the
        # stack compiles by itself)
        cache, _last, _chosen = jax.jit(functools.partial(
            serving._dense_prefill, model, None))(
                params, jnp.asarray([prompt[:6]]), 6)
        step = jax.jit(lambda cache, token, position, model=model:
                       model.apply({"params": params, "cache": cache},
                                   token, positions=position,
                                   mutable=serving._MUTABLE)[1]["cache"])
        trail = []
        for position in range(6, 70):
            cache = step(cache, jnp.asarray([[prompt[position]]]),
                         jnp.asarray([[position]]))
            trail.append(cache[first][mixer][leaf])
        assert trail[0].dtype == dtype
        states[dtype] = [np.asarray(s, np.float32) for s in trail]
    exact, rounded = states[jnp.float32], states[jnp.bfloat16]
    size = np.abs(exact[-1]).max()
    first = np.abs(rounded[0] - exact[0]).max() / np.abs(exact[0]).max()
    last = np.abs(rounded[-1] - exact[-1]).max() / size
    assert 0 < first <= 2.0 ** -8
    assert last > 2.0 ** -8, last    # the roundings gathered ...
    assert last < 0.1                # ... and it is still the state


# ------------------- (h) the routed experts' two roads


@pytest.fixture
def grouped_road(monkeypatch):
    """Every routed layer traced inside takes the grouped road (off
    the TPU the kernel runs in the interpreter). The road is picked
    while a program is traced, so what was traced under the other rule
    is dropped before and after."""
    jax.clear_caches()
    monkeypatch.setattr(moe, "experts_road",
                        lambda rows, config: "grouped")
    yield
    jax.clear_caches()


def test_the_grouped_road_serves_the_same_tokens_and_choices(
        share, served, grouped_road):
    """The engine of ``served`` again with the grouped road forced in
    every program, decode steps too: the same tokens, the same record
    of choices for take_decisions, and the road on every prefill's
    Launch record and in the counters; ``served``'s own engine, on
    the CPU, took the dense road everywhere."""
    _file, _dims, config, params = share
    dense_engine, prompts, new_tokens, done, records = served
    engine = _engine(config, params)
    assert _serve(engine, prompts, new_tokens) == done
    for request_id, record in records.items():
        again = engine.take_decisions(request_id)
        assert again["first"] == record["first"]
        assert set(again["layers"]) == set(record["layers"])
        for name, rows in record["layers"].items():
            np.testing.assert_array_equal(again["layers"][name], rows)
    for which, road in ((engine, "grouped"), (dense_engine, "dense")):
        prefills = [launch for launch in which._ring
                    if launch.kind == "prefill"]
        # (other tests have served more through ``served``'s engine)
        assert len(prefills) >= len(prompts)
        assert {launch.road for launch in prefills} == {road}
        assert all(launch.entry()["road"] == road
                   for launch in prefills)
        stats = which.step_stats()
        assert stats["prefills_grouped"] == \
            (stats["launches"]["prefill"] if road == "grouped" else 0)
    assert engine.step_stats()["prefills"] == len(prompts)
    # a decode launch's entry names no road
    assert "road" not in next(
        launch for launch in engine._ring
        if launch.kind == "decode").entry()


def test_the_engine_asks_the_layers_own_rule(share, monkeypatch):
    """_experts_road hands moe.experts_road the rows ONE prefill
    segment is traced with (the bucket, or the chunk of a chunked
    prefill) and the model's RoutedConfig."""
    _file, _dims, config, params = share
    asked = []
    monkeypatch.setattr(
        moe, "experts_road",
        lambda rows, cfg: asked.append((rows, cfg)) or "dense")
    whole = _engine(config, params)
    chunked = _engine(config, params, prefill_chunk=16)
    asked.clear()       # the layer itself asked while the caches were made
    assert whole._experts_road(64) == "dense"
    assert chunked._experts_road(64) == "dense"
    assert asked == [(64, config.experts), (16, config.experts)]

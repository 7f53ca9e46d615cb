"""A stack of sliding-window and full grouped-query attention layers
over routed ReGLU experts whose router reads the layer's input BEFORE
attention, through ContinuousBatcher: the full layers in the page
pool, the window layers in a ring a slot, a prefill in segments whose
attention is bounded by the segment and the window. CPU, tiny sizes,
seeded random weights, float32 on both sides so that a tolerance is
rounding alone; LOGITS are compared, not tokens: every served token's
logit has to lie within a tolerance of the plain reference's best at
its position (benchmark/reference/smallthinker_plain.py, float32
"highest", no cache), the reference run on the engine's own expert
choices, each of which has to be (within a tolerance) one the
reference would have made."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.models import inference as inf
from batch_shipyard_tpu.models import kv_pages, moe, serving
from batch_shipyard_tpu.models import transformer as tfm
from batch_shipyard_tpu.models.serving import Request
from benchmark import spec, weights

# The tiny sizes: d 64, 4 query over 2 K/V heads of 16, 8 experts top
# 2, one period [full, window, window, window] with a window of 24
# keys (NOT a whole number of the 16-token pages: a ring of
# ceil(24 / 16) + 1 = 3 pages), prefill in segments of 32.
WINDOW, PAGE, RING = 24, 16, 3
FILE = dict(
    hidden_size=64, head_dim=16, num_attention_heads=4,
    num_key_value_heads=2, moe_ffn_hidden_size=32,
    moe_num_primary_experts=8, moe_num_active_primary_experts=2,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True,
    num_hidden_layers=4, rope_layout=[0, 1, 1, 1],
    sliding_window_layout=[0, 1, 1, 1], sliding_window_size=WINDOW,
    rope_theta=1500000, rms_norm_eps=1e-6, vocab_size=256,
    tie_word_embeddings=False, model_module="window_moe",
    seeded_weights={"qk_gain": 1.5})
ENGINE = {"num_slots": 4, "max_decode_len": 128}
# float32 program against the float32 reference: the two differ by the
# order of their sums (blocks of keys with a running maximum against
# one softmax a row block, a matmul over all experts against one
# expert after another), some 1e-5 of a logit of size 1 to 4 after
# eight blocks; 1e-3 leaves two orders of room and is a thirtieth of
# what bfloat16 alone explains (check.tail_from 0.03).
GAP = 1e-3
# ... and a router logit (a unit-scale number) by some 1e-6.
SLACK = 1e-4


@dataclasses.dataclass
class Stack:
    file: dict
    dims: dict
    config: tfm.TransformerConfig
    params: dict
    module: object


@pytest.fixture(scope="module")
def stack():
    module = spec.load_model(FILE)
    dims = module.dims(FILE)
    config = dataclasses.replace(
        module.program_model(FILE, dims, ENGINE), dtype=jnp.float32,
        param_dtype=jnp.float32)
    params = weights.make_params(module.param_leaves(dims), 7,
                                 jnp.float32)
    return Stack(FILE, dims, config, params, module)


def _engine(config, params, **kwargs):
    kwargs.setdefault("num_slots", ENGINE["num_slots"])
    kwargs.setdefault("kv_page_size", PAGE)
    return serving.ContinuousBatcher(
        config, params, max_decode_len=ENGINE["max_decode_len"],
        **kwargs)


def _prompts(count, low=5, high=90, seed=0):
    rng = np.random.default_rng(seed)
    return {f"r{i}": [int(t) for t in rng.integers(
        1, FILE["vocab_size"], rng.integers(low, high))]
        for i in range(count)}


def _serve(engine, prompts, new_tokens, each_step=None):
    for request_id, prompt in prompts.items():
        engine.submit(Request(request_id, prompt,
                              max_new_tokens=new_tokens[request_id]))
    done = {}
    while engine.pending():
        for request_id, tokens in engine.step():
            done[request_id] = tokens
        if each_step is not None:
            each_step(engine)
    return done


def _judged(stack, prompt, served, record, params=None):
    sequence = prompt + served[:-1]
    handed = {name: jnp.asarray(rows) for name, rows in
              record["layers"].items()}
    logits, slacks = stack.module.teacher_forced_logits(
        params or stack.params, jnp.asarray(sequence, jnp.int32),
        jnp.arange(len(prompt) - 1, len(sequence)), stack.file,
        stack.dims, decisions=handed)
    best = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, jnp.asarray(served)[:, None],
                             axis=-1)[:, 0]
    return np.asarray(best - at), np.concatenate(
        [np.asarray(slack) for slack in slacks.values()])


@pytest.fixture(scope="module")
def served(stack):
    """Ten requests of 5 to 89 prompt tokens through four slots: most
    cross the window (24) and several page edges (16) in the prefill
    (segments of 32, buckets up to 128) and again while they decode;
    they share decode steps, wait for slots and reuse them."""
    engine = _engine(stack.config, stack.params)
    prompts = _prompts(10)
    rng = np.random.default_rng(1)
    new_tokens = {r: int(rng.integers(3, 30)) for r in prompts}
    done = _serve(engine, prompts, new_tokens)
    records = {r: engine.take_decisions(r) for r in done}
    return engine, prompts, new_tokens, done, records


# ------------------- (a) the engine against the reference


def test_prefill_then_decode_agree_with_the_references_full_pass(
        stack, served):
    _engine_, prompts, new_tokens, done, records = served
    assert set(done) == set(prompts)
    crossed = 0
    for request_id, tokens in done.items():
        assert len(tokens) == new_tokens[request_id]
        gaps, slack = _judged(stack, prompts[request_id], tokens,
                              records[request_id])
        assert gaps.max() < GAP, (request_id, gaps.max())
        assert slack.max() < SLACK, (request_id, slack.max())
        crossed += len(prompts[request_id]) + len(tokens) > 2 * WINDOW
    assert crossed >= 5      # contexts beyond the window and the ring


def test_with_the_window_taken_off_it_is_another_model(stack, served):
    """The control of the benchmark's check: the SAME weights through
    a program whose window layers attend over their whole context
    serve tokens the reference (which keeps the window) does not."""
    _engine_, prompts, new_tokens, _done, _records = served
    config = dataclasses.replace(
        stack.module.program_model(FILE, stack.dims, ENGINE,
                                   windows_off=True),
        dtype=jnp.float32, param_dtype=jnp.float32)
    assert not any(tfm.attention_windows(config))
    engine = _engine(config, stack.params)
    done = _serve(engine, prompts, new_tokens)
    worst = max(_judged(stack, prompts[r], done[r],
                        engine.take_decisions(r))[0].max()
                for r in done if len(prompts[r]) > 2 * WINDOW)
    assert worst > 100 * GAP, worst


def test_a_router_fed_the_experts_own_norm_is_another_model(stack,
                                                            served):
    """The router reads the ATTENTION block's normed input, across the
    block boundary: fed the experts block's own norm's output (the
    placement the other configurations have) the same weights choose
    other experts, which the reference rejects."""
    _engine_, prompts, new_tokens, _done, _records = served
    config = dataclasses.replace(stack.config,
                                 router_before_mixer=False)
    engine = _engine(config, stack.params)
    done = _serve(engine, prompts, new_tokens)
    slack = np.concatenate([
        _judged(stack, prompts[r], done[r],
                engine.take_decisions(r))[1] for r in done])
    assert (slack > 100 * SLACK).mean() > 0.2


def test_the_training_forward_is_the_references(stack):
    """No cache: TransformerLM's plain forward (the band by
    ops/attention.blockwise_mha over grouped K/V) gives the
    reference's logits at every position."""
    tokens = jnp.asarray(_prompts(1, 70, 71, seed=3)["r0"], jnp.int32)
    model = tfm.TransformerLM(stack.config)
    got = model.apply({"params": stack.params}, tokens[None])[0]
    want = stack.module.teacher_forced_logits(
        stack.params, tokens, jnp.arange(len(tokens)), stack.file,
        stack.dims)
    # float32 both sides, logits of size 1 to 4: the order of sums
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4)


def test_a_dense_cache_engine_serves_the_paged_engines_tokens(stack,
                                                              served):
    """Without pages the window is a mask over the slot's dense rows:
    the same mathematics, so the same tokens."""
    _engine_, prompts, new_tokens, done, _records = served
    engine = _engine(stack.config, stack.params, kv_page_size=None)
    some = {r: prompts[r] for r in list(prompts)[:4]}
    assert _serve(engine, some, new_tokens) == {
        r: done[r] for r in some}


@pytest.mark.parametrize("chunk", (None, 16))
def test_the_prefills_segments_do_not_move_the_result(stack, served,
                                                      chunk):
    """A bucket whole (prefill_chunk handed to the engine wins over
    its own rule) or in segments of 16, under a window: the logits the
    first token is sampled from agree to rounding, and the tokens
    served are the same."""
    _engine_, prompts, new_tokens, done, _records = served
    engine = _engine(stack.config, stack.params,
                     prefill_chunk=chunk or 128)
    assert engine.prefill_chunk == (chunk or 128)
    some = {r: prompts[r] for r in list(prompts)[:4]}
    assert _serve(engine, some, new_tokens) == {
        r: done[r] for r in some}


def test_the_engine_takes_its_segment_from_the_window(stack):
    """The least power of two that holds the widest window, and
    serving.SEGMENT_FLOOR at least (a window of 24 bounds nothing
    worth bounding), for a model that attends its inserts in blocks;
    a bucket whole without windows or without prefill_blocks."""
    from batch_shipyard_tpu.models import serving
    floor = serving.SEGMENT_FLOOR
    assert floor == 2048
    assert _engine(stack.config, stack.params).prefill_chunk == floor
    for config, segment in (
            (dataclasses.replace(stack.config, layer_windows=None), None),
            (dataclasses.replace(stack.config, prefill_blocks=False),
             None),
            (dataclasses.replace(stack.config, layer_windows=tuple(
                4096 if w else 0
                for w in stack.config.layer_windows)), 4096),
            (dataclasses.replace(stack.config, layer_windows=tuple(
                5000 if w else 0
                for w in stack.config.layer_windows)), 8192),
            (dataclasses.replace(stack.config, layer_windows=tuple(
                33 if w else 0
                for w in stack.config.layer_windows)), floor)):
        assert serving.window_segment(config) == segment


# ------------------- (b) the window layers' ring and its books


def test_the_cache_holds_a_ring_a_slot_beside_the_pool(stack):
    engine = _engine(stack.config, stack.params, kv_num_pages=40)
    kinds = tfm.layer_kinds(engine.config)
    windows = tfm.attention_windows(engine.config)
    assert windows == (0, WINDOW, WINDOW, WINDOW)
    assert tfm.paged_layer_count(engine.config) == 1
    assert tfm.ring_pages(engine.config, WINDOW) == RING
    width = 2 * 16
    for i, kind in enumerate(kinds):
        if kind != "attn":
            continue
        leaves = engine.cache[f"layer_{i}"]["attn"]
        if tfm.layer_window(engine.config, i):
            assert set(leaves) == {"k_ring", "v_ring", "length"}
            assert leaves["k_ring"].shape == (4 * RING, PAGE, width)
        else:
            assert set(leaves) == {"k_pages", "v_pages", "block_table",
                                   "length"}
            assert leaves["k_pages"].shape == (41, PAGE, width)


def test_no_window_layer_ever_holds_more_than_its_ring(stack, recorder):
    """Through seat, grow, release and a dry pool's preemption the
    window group never holds more than ceil(W / page) + 1 pages a
    slot, the pool's own invariants hold after every step, and what
    the rows say is what the books say."""
    from batch_shipyard_tpu.trace import spans as trace_spans
    engine = _engine(stack.config, stack.params, kv_num_pages=12,
                     overcommit=True, prefill_chunk=32)
    seen = []

    def each_step(engine):
        engine.pages.check()
        state = engine.occupancy()
        seated = [slot for slot in engine._slots if slot.decoding()]
        assert state["window_pages_total"] == 4 * RING
        assert state["window_pages_in_use"] == sum(
            min(-(-slot.held_tokens() // PAGE), RING)
            for slot in seated) <= len(seated) * RING
        assert state["kv_tokens_full"] == state["live_tokens"]
        assert state["kv_tokens_window"] == sum(
            min(slot.held_tokens(), WINDOW) for slot in seated)
        seen.append(state)

    prompts = _prompts(8, 30, 60, seed=5)
    done = _serve(engine, prompts, dict.fromkeys(prompts, 40),
                  each_step)
    assert set(done) == set(prompts) and engine.preemptions > 0
    assert max(s["window_pages_in_use"] for s in seen) == 4 * RING
    assert any(s["kv_tokens_window"] < s["kv_tokens_full"]
               for s in seen)
    rows = [row["attrs"] for row in recorder()
            if row["kind"] == trace_spans.SPAN_SERVE_STEP]
    assert rows and all(
        {"window_pages_in_use", "window_pages_total", "kv_tokens_full",
         "kv_tokens_window"} <= set(row) for row in rows)
    prefills = [launch for row in rows for launch in row["landed"]
                if launch["kind"] == "prefill"]
    assert prefills and all(
        launch["chunks"] == -(-launch["bucket"] // 32)
        for launch in prefills)


def test_preempted_requests_are_served_what_an_ample_pool_serves(stack):
    prompts = _prompts(6, 30, 60, seed=6)
    new_tokens = dict.fromkeys(prompts, 30)
    tight = _engine(stack.config, stack.params, kv_num_pages=10,
                    overcommit=True)
    ample = _engine(stack.config, stack.params)
    assert _serve(tight, prompts, new_tokens) == \
        _serve(ample, prompts, new_tokens)
    assert tight.preemptions > 0


def test_ring_occupancy_by_hand():
    assert kv_pages.ring_occupancy([1, 16, 17, 200], 6, 16, 3, 24) == {
        "window_pages_in_use": 1 + 1 + 2 + 3, "window_pages_total": 18,
        "kv_tokens_full": 234, "kv_tokens_window": 1 + 16 + 17 + 24}
    assert kv_pages.ring_occupancy([], 6, 16, 3, 24)[
        "window_pages_in_use"] == 0


def test_a_matched_prefix_never_stands_in_for_a_window_layers_keys(
        stack):
    """A request whose prompt matches indexed pages SHARES them in the
    full layers (one copy) and still runs its whole prompt: a window
    layer's ring is the slot's own and no page names it. It is served
    exactly what an engine without the index serves."""
    rng = np.random.default_rng(9)
    prefix = [int(t) for t in rng.integers(1, 256, 48)]
    prompts = {f"p{i}": prefix + [int(t) for t in
                                   rng.integers(1, 256, 9 + 7 * i)]
               for i in range(3)}
    new_tokens = dict.fromkeys(prompts, 12)
    shared = _engine(stack.config, stack.params, num_slots=1)
    cold = _engine(stack.config, stack.params, num_slots=1,
                   prefix_cache=False)
    paths = []
    shared.on_admit = lambda request_id: None
    done = _serve(shared, prompts, new_tokens,
                  lambda engine: paths.extend(
                      entry["path"] for entry in engine._admitted))
    assert done == _serve(cold, prompts, new_tokens)
    assert shared.prefix_stats()["hit_tokens"] == 2 * 48
    assert "recomputed" in paths and "shared" not in paths
    shared.pages.check()


def test_idle_slots_are_parked_in_the_rings_too(stack):
    """A slot whose request has ended leaves the later steps with its
    cursor at 0 in the window layers as in the full ones: the kernel
    then walks one page of its ring, not its last request's."""
    engine = _engine(stack.config, stack.params)
    _serve(engine, _prompts(2, 40, 50), {"r0": 3, "r1": 12})
    lengths = [np.asarray(engine.cache[f"layer_{i}"]["attn"]["length"])
               for i, kind in enumerate(tfm.layer_kinds(engine.config))
               if kind == "attn"]
    assert len(lengths) == 4
    for length in lengths:
        assert (length > 0).sum() == 1      # r1's slot alone
        assert (length == lengths[0]).all()


def test_a_draft_model_is_refused_for_a_window_target(stack):
    with pytest.raises(ValueError, match="window layer"):
        _engine(stack.config, stack.params,
                speculative=serving.SpeculativeConfig(
                    dataclasses.replace(stack.config, block_kinds=None,
                                        layer_windows=None,
                                        layer_rope=None, n_layers=1),
                    None, gamma=2))


def test_the_grouped_kernel_serves_the_gathers_tokens(stack, served):
    """paged_attention_impl "kernel" MEANS the grouped Pallas kernel
    for this stack's pools and rings (interpret mode here): the same
    tokens as the XLA gather."""
    from jax.experimental.pallas import tpu as pltpu
    _engine_, prompts, new_tokens, done, _records = served
    some = {r: prompts[r] for r in list(prompts)[:3]}
    config = dataclasses.replace(stack.config,
                                 paged_attention_impl="kernel")
    with pltpu.force_tpu_interpret_mode():
        engine = _engine(config, stack.params)
        assert _serve(engine, some, new_tokens) == {
            r: done[r] for r in some}


# ------------------- (c) the routed layer's second rule


def test_softmax_over_the_chosen_by_hand():
    logits = jnp.asarray([[2.0, -1.0, 0.5, 3.0], [0.0, 0.0, 1.0, -2.0]])
    chosen, weights = moe.route_softmax(logits, 2)
    assert chosen.tolist() == [[3, 0], [2, 0]]
    e = np.exp
    np.testing.assert_allclose(
        np.asarray(weights),
        [[e(3) / (e(3) + e(2)), e(2) / (e(3) + e(2))],
         [e(1) / (e(1) + 1), 1 / (e(1) + 1)]], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0,
                               rtol=1e-6)


@pytest.mark.parametrize("gate_act", ("silu", "relu"))
def test_no_shared_width_makes_no_shared_leaf(gate_act):
    config = moe.RoutedConfig(d_model=16, n_experts=4, top_k=2,
                              d_expert=8, d_shared=0, gated=True,
                              gate_act=gate_act, scoring="softmax")
    layer = moe.RoutedExperts(config, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 16))
    variables = layer.init(jax.random.PRNGKey(1), x)
    assert set(variables["params"]) == {
        "router_kernel", "experts_up", "experts_down", "experts_gate"}
    # the layer IS the sum of the chosen experts, by hand
    p = variables["params"]
    out = layer.apply({"params": p}, x)[0]
    logits = x[0] @ p["router_kernel"]
    chosen, weights = moe.route_softmax(logits, 2)
    act = jax.nn.relu if gate_act == "relu" else jax.nn.silu
    want = sum(
        weights[:, j, None] * jnp.einsum(
            "tf,tfd->td",
            act(jnp.einsum("td,tdf->tf", x[0],
                           p["experts_gate"][chosen[:, j]]))
            * jnp.einsum("td,tdf->tf", x[0],
                         p["experts_up"][chosen[:, j]]),
            p["experts_down"][chosen[:, j]]) for j in range(2))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5)


def test_a_bfloat16_router_is_the_lower_precision():
    """router_dtype bfloat16 (a check's control): two logits 0.001
    apart are one bfloat16 number, so the choice falls to the lower
    index where the float32 router takes the larger."""
    x = jnp.zeros((1, 1, 16), jnp.float32).at[0, 0, 0].set(1.0)
    kernel = jnp.zeros((16, 4), jnp.float32).at[0].set(
        jnp.asarray([0.5, 1.0, 1.001, -1.0]))
    chosen = {}
    for name in ("float32", "bfloat16"):
        layer = moe.RoutedExperts(moe.RoutedConfig(
            d_model=16, n_experts=4, top_k=1, d_expert=8, d_shared=0,
            gated=True, scoring="softmax",
            router_dtype=jnp.dtype(name).type), dtype=jnp.float32)
        params = dict(layer.init(jax.random.PRNGKey(1), x)["params"],
                      router_kernel=kernel)
        _out, sown = layer.apply({"params": params}, x,
                                 mutable=["decisions"])
        chosen[name] = int(sown["decisions"]["chosen"][0][0, 0, 0])
    assert chosen == {"float32": 2, "bfloat16": 1}


def test_an_unknown_rule_is_refused():
    layer = moe.RoutedExperts(moe.RoutedConfig(scoring="tanh"))
    with pytest.raises(ValueError, match="scoring"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 2, 512)))


def test_per_layer_lists_are_held_to_the_layer_count(stack):
    short = dataclasses.replace(stack.config, layer_windows=(0, 24))
    with pytest.raises(ValueError, match="layer_windows"):
        tfm.layer_window(short, 0)
    short = dataclasses.replace(stack.config, layer_rope=(True,))
    with pytest.raises(ValueError, match="layer_rope"):
        tfm.layer_rope(short, 0)
    first = dataclasses.replace(
        stack.config, block_kinds=("experts", "attn") * 4)
    with pytest.raises(ValueError, match="router_before_mixer"):
        tfm.TransformerLM(first).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def test_each_window_layer_has_its_own_scope_in_the_step_program(
        stack):
    engine = _engine(stack.config, stack.params)
    text = serving._decode_step.lower(
        engine.model, inf.SamplingConfig(), engine.params, engine.cache,
        engine._tokens, engine._positions, engine._active,
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    for i in (0, 2, 4, 6):
        assert f"layer_{i}/attn/" in text

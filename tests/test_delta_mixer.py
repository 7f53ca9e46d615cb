"""The gated delta-rule mixer (models/delta.py), the attention gate and
the gated experts, each against the plain reference of the stack they
were written for (benchmark/reference/hybrid_delta_moe_plain.py:
float32 "highest", the delta rule token by token). CPU, tiny sizes,
float32 on both sides, so every tolerance below is the order of
float32 sums alone: 1e-5 on values of size about 1 (the chunked form
solves a triangular system where the recurrence multiplies through;
observed differences are 1e-6). The whole stack through
ContinuousBatcher is tests/test_hybrid_serving.py's ``delta`` cases."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.models import delta, moe
from batch_shipyard_tpu.models import transformer as tfm
from benchmark.reference import hybrid_delta_moe_plain as plain

HEADS, WIDTH = 4, 16
# one head that hardly decays, one at A = -1, two that forget at once
# (e**3 and e**6 nats a token: exp(-G) would overflow inside a chunk)
A_LOG = jnp.asarray([-6.0, 0.0, 3.0, 6.0])


def _rows(length, batch=2, seed=0):
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (batch, length, HEADS, WIDTH)
    q = delta.unit(jax.random.normal(key[0], shape)) * WIDTH ** -0.5
    k = delta.unit(jax.random.normal(key[1], shape))
    v = jax.random.normal(key[2], shape)
    g = -jnp.exp(A_LOG)[:, None] * jax.nn.softplus(
        jax.random.normal(key[3], shape))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(key[4], shape[:3]))
    return q, k, v, g, beta


def _stepped(q, k, v, g, beta, state):
    outs = []
    for t in range(q.shape[1]):
        o, state = delta.delta_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                    beta[:, t], state)
        outs.append(o)
    return jnp.stack(outs, 1), state


@pytest.mark.parametrize("length,chunk", [
    (37, 8),      # four whole chunks and one padded
    (5, 8),       # shorter than a chunk
    (64, 16),     # whole chunks
    (19, 64),     # one chunk, as long as the call
])
def test_the_chunked_form_is_the_step_is_the_references_scan(length,
                                                             chunk):
    rows = _rows(length)
    zero = jnp.zeros((2, HEADS, WIDTH, WIDTH))
    o, last = delta.delta_scan(*rows, zero, chunk)
    assert bool(jnp.isfinite(o).all())       # no overflow under decay
    stepped, stepped_last = _stepped(*rows, zero)
    np.testing.assert_allclose(o, stepped, atol=1e-5)
    np.testing.assert_allclose(last, stepped_last, atol=1e-5)
    for b in range(2):
        want, want_last = plain.delta_rule(*(t[b] for t in rows))
        np.testing.assert_allclose(o[b], want, atol=1e-5)
        np.testing.assert_allclose(last[b], want_last, atol=1e-5)
    # the heads differ as their decays do: the first remembers
    assert float(jnp.abs(last[:, 0]).max()) > 0.5
    assert float(jnp.abs(last[:, 3]).max()) < 2.5


def test_the_chunked_form_goes_on_from_a_given_state():
    rows = _rows(21, seed=3)
    state = jax.random.normal(jax.random.PRNGKey(9),
                              (2, HEADS, WIDTH, WIDTH))
    o, last = delta.delta_scan(*rows, state, 8)
    stepped, stepped_last = _stepped(*rows, state)
    np.testing.assert_allclose(o, stepped, atol=1e-5)
    np.testing.assert_allclose(last, stepped_last, atol=1e-5)


def test_rows_with_no_write_and_no_decay_leave_the_state_alone():
    """What valid_len makes of bucket padding: beta = g = 0 past the
    sequence's own 11 tokens of 32, in whole chunks and in a chunk
    that is part padding."""
    q, k, v, g, beta = _rows(32, seed=4)
    own = (jnp.arange(32) < 11)[None, :, None]
    padded = (q, k, v, jnp.where(own[..., None], g, 0.0),
              jnp.where(own, beta, 0.0))
    zero = jnp.zeros((2, HEADS, WIDTH, WIDTH))
    _o, want = delta.delta_scan(*(t[:, :11] for t in (q, k, v, g, beta)),
                                zero, 8)
    o, last = delta.delta_scan(*padded, zero, 8)
    np.testing.assert_allclose(last, want, atol=1e-6)
    # a padding row still READS the frozen state
    assert float(jnp.abs(o[:, 11:]).max()) > 0
    _o, moved = delta.delta_scan(q, k, v, g, beta, zero, 8)
    assert float(jnp.abs(moved - want).max()) > 1e-2


def _config(**changes):
    return dataclasses.replace(tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=4, n_kv_heads=2,
        d_head=8, dtype=jnp.float32, param_dtype=jnp.float32,
        use_rope=False, norm_eps=1e-5,
        delta=delta.DeltaConfig(n_heads=HEADS, head_dim=WIDTH,
                                gate_rank=8, chunk=8)), **changes)


def _seeded(module, *inputs, seed=0):
    """The module's own tree with every leaf drawn anew (its zeros and
    ones would hide a leaf that is not wired in)."""
    shapes = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), *inputs))["params"]
    leaves, tree = jax.tree_util.tree_flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        0.3 * jax.random.normal(key, leaf.shape, leaf.dtype)
        for key, leaf in zip(keys, leaves)])


def test_the_mixer_is_the_references_layer_in_one_call_and_by_steps():
    """37 tokens at once (chunks of 8), and the same tokens as an
    11-token prefill in a bucket of 16 then one-token steps through
    the cache leaves, against the reference's whole layer."""
    cfg = _config()
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 37, 32))
    mixer = delta.DeltaMixer(cfg)
    params = _seeded(mixer, x)
    want = plain.kda(x[0], params, heads=HEADS, width=WIDTH, eps=1e-5)
    np.testing.assert_allclose(mixer.apply({"params": params}, x)[0],
                               want, atol=1e-5)
    serving = delta.DeltaMixer(dataclasses.replace(cfg, decode=True))
    bucket = jnp.pad(x[:, :11], [(0, 0), (0, 5), (0, 0)])
    cache = jax.tree_util.tree_map(jnp.zeros_like, serving.init(
        jax.random.PRNGKey(0), bucket)["cache"])
    assert set(cache) == set(delta.STATE_LEAVES)
    out, mutated = serving.apply({"params": params, "cache": cache},
                                 bucket, 11, mutable=["cache"])
    np.testing.assert_allclose(out[0, :11], want[:11], atol=1e-5)
    # (the one-token step as ONE compiled program, not an eager
    # operation at a time)
    step = jax.jit(lambda cache, row: serving.apply(
        {"params": params, "cache": cache}, row, mutable=["cache"]))
    for t in range(11, 37):
        out, mutated = step(mutated["cache"], x[:, t:t + 1])
        np.testing.assert_allclose(out[0, 0], want[t], atol=1e-5)
    # three tails in one leaf: the last K-1 rows of q | k | v
    assert mutated["cache"]["qkv_tail"].shape == (1, 3, 3 * HEADS * WIDTH)


def test_gated_attention_is_the_references():
    cfg = _config(attn_output_gate=True)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 23, 32))
    attention = tfm.Attention(cfg)
    params = _seeded(attention, x, jnp.arange(23))
    assert "gate_proj" in params
    want = plain.attention(x[0], params, q_heads=4, kv_heads=2)
    np.testing.assert_allclose(
        attention.apply({"params": params}, x, jnp.arange(23))[0], want,
        atol=1e-5)
    # ... and without the field there is no gate, as before
    plainer = tfm.Attention(_config())
    assert "gate_proj" not in _seeded(plainer, x, jnp.arange(23))


def _routed(held=4, first=0, n=8, gated=True, k=2):
    return moe.RoutedConfig(d_model=32, n_experts=n, top_k=k,
                            d_expert=24, d_shared=24, experts_held=held,
                            first_expert=first, gated=gated)


def test_gated_dense_experts_is_a_loop_over_rows_and_experts():
    layer = moe.RoutedExperts(_routed(), dtype=jnp.float32)
    rows = jax.random.normal(jax.random.PRNGKey(3), (1, 12, 32))
    w = _seeded(layer, rows)
    chosen, weigh = moe.route_sigmoid(
        rows[0] @ w["router_kernel"], w["e_score_correction_bias"], 2, 1.0)
    got = moe.dense_experts(rows[0], chosen, weigh, w["experts_up"],
                            w["experts_down"], 0, w["experts_gate"])
    for i in range(12):
        want = jnp.zeros((32,))
        for expert, weight in zip(chosen[i], weigh[i]):
            if expert < 4:
                want += weight * plain.swiglu(
                    rows[0, i], w["experts_gate"][expert],
                    w["experts_up"][expert], w["experts_down"][expert])
        np.testing.assert_allclose(got[i], want, atol=1e-5)


def test_experts_without_a_gate_compute_what_they_did_before():
    """The non-gated layer's result, BIT FOR BIT, against the two
    matmuls as they stood before the gate came (the state-space
    stack's compiled steps must not move)."""
    layer = moe.RoutedExperts(_routed(gated=False), dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 96, 32))
    w = _seeded(layer, x)
    assert not {"experts_gate", "shared_gate"} & set(w)
    rows = x[0].astype(jnp.bfloat16)
    up, down = (w[name].astype(jnp.bfloat16)
                for name in ("experts_up", "experts_down"))
    chosen, weights = moe.route_sigmoid(
        jnp.dot(rows, w["router_kernel"].astype(jnp.bfloat16),
                preferred_element_type=jnp.float32),
        w["e_score_correction_bias"], 2, 1.0)
    weigh = jnp.sum(jnp.where(
        chosen[:, :, None] == jnp.arange(4), weights[:, :, None], 0.0),
        axis=1)
    hidden = jax.lax.dot_general(
        rows, up, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    hidden = (jnp.square(jax.nn.relu(hidden))
              * weigh[:, :, None]).astype(rows.dtype)
    before = jax.lax.dot_general(
        hidden, down, (((1, 2), (0, 1)), ((), ())),
        preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(
        moe.dense_experts(rows, chosen, weights, up, down, 0), before)
    shared = jnp.dot(rows, w["shared_up"].astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    shared = jnp.dot(
        jnp.square(jax.nn.relu(shared)).astype(jnp.bfloat16),
        w["shared_down"].astype(jnp.bfloat16),
        preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(
        layer.apply({"params": w}, x)[0],
        (before + shared).astype(jnp.bfloat16))


def test_the_eight_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """Sixteen experts over eight chips, two each, top-4, one shared
    expert that every chip computes alike: the eight shares' outputs
    with the shared expert counted ONCE are the uncut reference's
    layer; in the program (RoutedExperts told which two it holds) and
    in the reference (handed the same two)."""
    whole = moe.RoutedExperts(_routed(held=16, n=16, k=4),
                              dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 40, 32))
    w = _seeded(whole, x)
    own = jnp.full((40, 4), -1, jnp.int32)
    sizes = {"top_k": 4, "scale": 1.0}
    want, _slack = plain.experts(x[0], w, own, first=0, **sizes)
    shared = plain.swiglu(x[0], w["shared_gate"], w["shared_up"],
                          w["shared_down"])
    total, total_ref = -7 * shared, -7 * shared
    for chip in range(8):
        held = dict(w, **{name: w[name][2 * chip:2 * chip + 2]
                          for name in ("experts_gate", "experts_up",
                                       "experts_down")})
        layer = moe.RoutedExperts(
            _routed(held=2, first=2 * chip, n=16, k=4),
            dtype=jnp.float32)
        total += layer.apply({"params": held}, x)[0]
        total_ref += plain.experts(x[0], held, own, first=2 * chip,
                                   **sizes)[0]
    # sixteen terms of size up to 10 summed in another order: 1e-5 OF
    # the value
    np.testing.assert_allclose(total_ref, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)
    # a share is not the layer: most of a row's weight lies elsewhere
    assert float(jnp.abs(want - shared).max()) > 0.1

"""The configuration with latent attention under sandwich norms, routed
experts and its own drafter, THROUGH THE ENGINE at a small size, and
its reference beside it (benchmark/reference/openpangu_plain.py,
benchmark/models/latent_moe_mtp.py): the tree against model.init; the
reference's stack and module logits against the program's forward
without a cache; prefill then decode through the latent pool against
the reference's full forward on logits, float32 to rounding and
bfloat16 inside stated tolerances; a prefix match on latent pages that
skips its prefill and serves the same tokens; the module deciding how
many tokens land and never which; the thirty-two (here: two) chips'
shares of a sparse layer adding up to the uncut layer.

Tolerances: the float32 engine holds 3e-4 on logits of order 1 (the
engine sums in pages and in the absorbed order, the reference in row
blocks of the expanded form: rounding alone). The bfloat16 engine is
held on the reference's own judgement as the benchmark's check makes
it (the gap of the served token under the reference's best, the share
of routing choices the reference would reject), at limits a flipped
choice or a wrong position breaks by orders: bfloat16 weights,
activations and cache rows move a logit by about 2e-2, a routed score
by about 4e-3."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.models import serving
from batch_shipyard_tpu.models import transformer as tfm
from benchmark import check, harness, spec, weights
from benchmark.reference import openpangu_plain as plain

CONFIG = "openpangu-ultra-moe-718b-serve-1chip"


@pytest.fixture(scope="module")
def tiny():
    """The configuration at its rehearse_tiny size: (file, module,
    dims, float32 seeded weights, the program's float32 model
    configuration)."""
    config = harness.merged(spec.load_config(CONFIG), True)
    module = spec.load_model(config)
    dims = module.dims(config)
    params = weights.make_params(module.param_leaves(dims), 3,
                                 jnp.float32)
    program = dataclasses.replace(
        module.program_model(config, dims, config["engine"]),
        dtype=jnp.float32, param_dtype=jnp.float32)
    return config, module, dims, params, program


def _engine(program, params, **more):
    return serving.ContinuousBatcher(program, params, **{**dict(
        num_slots=3, max_decode_len=128, kv_page_size=8,
        kv_num_pages=40), **more})


def _serve(engine, requests):
    for request in requests:
        engine.submit(dataclasses.replace(request))
    done = {}
    while engine.pending():
        for request_id, tokens in engine.step():
            done[request_id] = tokens
    return done


def _requests(vocab, count, seed, low=5, high=40, new=(6, 20)):
    rng = np.random.default_rng(seed)
    return [serving.Request(
        f"r{r}", rng.integers(1, vocab, int(rng.integers(low, high))
                              ).tolist(),
        max_new_tokens=int(rng.integers(*new))) for r in range(count)]


def test_the_tree_is_the_programs_tree(tiny):
    _config, _module, _dims, params, program = tiny
    made = tfm.TransformerLM(program).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    assert jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), made) \
        == jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), params)
    assert set(params["mtp"]) == {"embed_norm", "hidden_norm", "proj",
                                  "layer_0", "layer_1", "norm"}
    assert set(params["layer_1"]) == {"norm", "mlp", "post_norm"}
    assert set(params["layer_3"]) == {"norm", "experts", "post_norm"}


def test_stack_and_module_logits_match_the_reference(tiny):
    """The forward WITHOUT a cache (expanded form) against the plain
    reference, the module's logits too; then each part the mechanism
    brings moved alone (the rotary key's column of W_dkv, a post norm's
    scale): the reference's logits move and the program follows."""
    config, module, dims, params, program = tiny
    model = tfm.TransformerLM(program)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(1, dims["vocab"], 70), jnp.int32)
    rows = jnp.arange(70)

    def program_logits(tree):
        (logits, hidden), _ = model.apply(
            {"params": tree}, tokens[None], stack_hidden=True,
            mutable=["decisions"])
        following = jnp.concatenate([tokens[1:], tokens[:1] * 0])
        drafted, _ = model.apply(
            {"params": tree}, following[None], mtp_hidden=hidden,
            mutable=["decisions"])
        return logits[0], drafted[0]

    want, want_module = module.teacher_forced_logits(
        params, tokens, rows, config, dims, mtp_rows=rows[:-1])
    got, got_module = program_logits(params)
    np.testing.assert_allclose(got, want, atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(got_module[:-1], want_module, atol=3e-4,
                               rtol=3e-4)
    for path, change in (
            (("layer_2", "attn", "kv_down", "kernel"),
             lambda w: w.at[:, dims["kv_rank"]:].multiply(3.0)),
            (("layer_4", "post_norm", "scale"),
             lambda w: jnp.linspace(0.3, 3.0, w.shape[0]))):
        changed = jax.tree_util.tree_map(lambda x: x, params)
        leaf = changed
        for key in path[:-1]:
            leaf = leaf[key]
        leaf[path[-1]] = change(leaf[path[-1]])
        moved = module.teacher_forced_logits(changed, tokens, rows,
                                             config, dims)
        assert float(jnp.max(jnp.abs(moved - want))) > 1e-2
        np.testing.assert_allclose(program_logits(changed)[0], moved,
                                   atol=3e-4, rtol=3e-4)


def test_prefill_then_decode_through_the_pool_is_the_references_forward(
        tiny):
    """The float32 engine: five prompts of 30 to 44 tokens prefilled
    into pages of 8 (expanded form), then 14 to 21 tokens each by
    decode steps of two positions (absorbed form over the pool, the
    module's rows beside the stack's), three slots at a time. The
    engine is greedy, so what it lands IS the reference's best token
    wherever the logits agree: every served token's gap under the
    reference's best logit is held at rounding, the reference run as
    one full forward on the engine's own routed choices, and so is
    every choice's slack."""
    config, module, dims, params, program = tiny
    engine = _engine(program, params)
    requests = _requests(dims["vocab"], 5, seed=1, low=30, high=45,
                         new=(14, 22))
    done = _serve(engine, requests)
    layers = spec.decision_layers(module, config, dims)
    finished = [{"idx": i, "prompt": r.prompt,
                 "tokens": done[r.request_id],
                 "decisions": engine.take_decisions(r.request_id)}
                for i, r in enumerate(requests)]
    readings = check.serve_gaps(params, module, config, dims, finished,
                                layers)
    assert len(readings["gaps"]) == sum(
        len(done[r.request_id]) for r in requests)
    # a served token is the reference's best but for ties at rounding
    assert max(readings["gaps"]) < 3e-4
    # and every routed choice is the reference's own to a rounding of
    # the scores (5 decision layers a position), but the module's at a
    # request's LAST position: its next token is the one teacher
    # forcing never feeds, and the check's padding stands in for it
    assert readings["positions_unrecorded"] == 0
    off = [slack for slack in readings["slack"] if slack > 1e-4]
    assert len(off) <= len(requests), off
    assert engine.step_stats()["mtp_drafted"] > 0


def test_the_bfloat16_engine_is_inside_the_checks_limits(tiny):
    """bfloat16 weights, activations and cache rows, as served: the
    benchmark's own numbers over 4 requests, beside limits three times
    what a sound engine reads here (gap_tail_mean about 2e-5,
    routing_rejected_share about 3e-3 at slack_from 0.02): the absorbed
    and expanded paths round differently and agree inside them."""
    config, module, dims, _params, program = tiny
    params = weights.make_params(module.param_leaves(dims), 3,
                                 jnp.bfloat16)
    served = dataclasses.replace(program, dtype=jnp.bfloat16,
                                 param_dtype=jnp.bfloat16)
    engine = _engine(served, params)
    requests = _requests(dims["vocab"], 4, seed=2, low=30, high=45,
                         new=(14, 22))
    done = _serve(engine, requests)
    layers = spec.decision_layers(module, config, dims)
    finished = [{"idx": i, "prompt": r.prompt,
                 "tokens": done[r.request_id],
                 "decisions": engine.take_decisions(r.request_id)}
                for i, r in enumerate(requests)]
    readings = check.serve_gaps(params, module, config, dims, finished,
                                layers)
    numbers = {**check.gap_numbers(readings["gaps"], 0.03),
               **check.routing_numbers(readings, 0.02)}
    assert numbers["gap_tail_mean"] <= 1e-3
    assert numbers["routing_rejected_share"] <= 0.02


def test_a_prefix_match_on_latent_pages_skips_its_prefill(tiny):
    """Every layer is pooled full attention: a second request with the
    first's 24-token head SHARES its three latent pages in all six
    pooled layers (the module's among them), prefills its suffix alone
    (path "shared") and lands the tokens a cold engine lands."""
    _config, _module, dims, params, program = tiny
    rng = np.random.default_rng(4)
    head = rng.integers(1, dims["vocab"], 24).tolist()
    first = serving.Request("a", head + [5, 6, 7], max_new_tokens=6)
    second = serving.Request("b", head + [9, 8, 7, 6, 5],
                             max_new_tokens=9)
    warm = _engine(program, params)
    assert not warm._recomputes_matched
    _serve(warm, [first])
    before = warm.step_stats()["prefill_tokens"]
    got = _serve(warm, [second])["b"]
    stats = warm.prefix_stats()
    assert stats["hit_tokens"] == 24 and stats["hit_pages"] == 3
    # the suffix alone went through the model
    assert warm.step_stats()["prefill_tokens"] - before == 5
    cold = _engine(program, params, prefix_cache=False)
    assert _serve(cold, [second])["b"] == got
    warm.pages.check()


def test_the_module_changes_how_many_tokens_land_never_which(tiny):
    """_verify_and_draft over the latent mixer: the same requests
    through an engine whose model has the module and one whose model
    has none land the same tokens; some steps of the first land two
    (weights under which the module agrees with the stack)."""
    _config, _module, dims, params, program = tiny
    d = dims["d_model"]

    def agreeing(tree, path=()):
        out = {}
        for key, value in tree.items():
            if isinstance(value, dict):
                out[key] = agreeing(value, path + (key,))
            elif key in ("experts_down", "shared_down") or (
                    key == "kernel"
                    and path[-1] in ("o_proj", "down_proj")):
                out[key] = jnp.zeros_like(value)
            else:
                out[key] = value
        return out

    # every sublayer's output zero (a post norm of zeros is zeros): the
    # stream stays the embedding, the token after t a function of t;
    # the module passes the NEXT token's embedding on, mixed with some
    # of the current stream so that some drafts miss
    tree = agreeing(params)
    eye = jnp.eye(d, dtype=jnp.float32)
    tree["mtp"]["proj"]["kernel"] = jnp.concatenate([eye, 0.6 * eye])
    requests = _requests(dims["vocab"], 6, seed=5)
    drafting = _engine(program, tree)
    with_module = _serve(drafting, requests)
    without = dataclasses.replace(program, mtp_modules=0)
    plain_tree = {k: v for k, v in tree.items() if k != "mtp"}
    assert _serve(_engine(without, plain_tree), requests) == with_module
    stats = drafting.step_stats()
    assert 0 < stats["mtp_accepted"] < stats["mtp_drafted"]


def test_the_chips_parts_of_a_sparse_layer_add_up(tiny):
    """The share: each of the chips that share a layer computes the
    routed sum over ITS experts; the parts, the shared expert counted
    once, add up to the uncut reference layer (all experts held)."""
    _config, _module, dims, _params, _program = tiny
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

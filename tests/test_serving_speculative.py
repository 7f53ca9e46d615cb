"""Speculative decoding INSIDE the continuous batching engine
(models/serving.py SpeculativeConfig): per-slot ragged draft/verify —
slots advance 1..gamma+1 tokens per step — must stay greedy-exact
against the non-speculative engine across mixed accept/reject slots,
mid-draft stops, mid-flight admission, dense AND paged KV, plus the
stats/plumbing."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.models import inference as inf
from batch_shipyard_tpu.models import serving
from batch_shipyard_tpu.models import transformer as tfm

CFG = tfm.TransformerConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=2, d_head=16,
    d_ff=64, max_seq_len=64, dtype=jnp.float32,
    param_dtype=jnp.float32)
DCFG = tfm.TransformerConfig(
    vocab_size=97, d_model=16, n_layers=1, n_heads=2, d_head=8,
    d_ff=32, max_seq_len=64, dtype=jnp.float32,
    param_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return tfm.TransformerLM(CFG).init(
        jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture(scope="module")
def dparams():
    return tfm.TransformerLM(DCFG).init(
        jax.random.PRNGKey(3), jnp.zeros((1, 8), jnp.int32))["params"]


@pytest.fixture(scope="module")
def noisy_params(params):
    """A lightly-perturbed copy of the target as draft: agrees often
    but not always — every round mixes accepted and rejected drafts
    across slots (the ragged per-slot commit path)."""
    rng = np.random.RandomState(11)
    return jax.tree_util.tree_map(
        lambda p: p + jnp.asarray(0.02 * rng.randn(*p.shape),
                                  p.dtype), params)


_REF_RUNS: dict = {}


def reference_greedy(params, prompt, num_tokens, max_decode_len=64):
    """Lockstep greedy reference. The decoder fn is memoized per
    max_decode_len (and jax caches compiles per (prompt_len,
    num_tokens)) — tests below standardize prompt lengths and token
    counts so the suite pays a handful of reference compiles, not one
    per call."""
    run = _REF_RUNS.get((id(params), max_decode_len))
    if run is None:
        run, _model = inf.make_decoder(CFG, params,
                                       max_decode_len=max_decode_len)
        _REF_RUNS[(id(params), max_decode_len)] = run
    tokens, _cache = run(jnp.asarray([prompt], jnp.int32), num_tokens,
                         jax.random.PRNGKey(0))
    return list(np.asarray(tokens[0, len(prompt):]))


def _drain(engine, max_steps=400):
    results = {}
    for _ in range(max_steps):
        for rid, toks in engine.step():
            results[rid] = toks
        if not engine.pending():
            break
    assert not engine.pending(), "engine failed to drain"
    return results


def _spec_engine(params, draft_cfg, draft_params, gamma=4,
                 num_slots=2, kv_page_size=None, **kw):
    return serving.ContinuousBatcher(
        CFG, params, num_slots=num_slots, max_decode_len=64,
        kv_page_size=kv_page_size,
        speculative=serving.SpeculativeConfig(
            draft_cfg, draft_params, gamma=gamma), **kw)


def test_mixed_acceptance_matches_nonspeculative(params,
                                                 noisy_params):
    """The core equivalence: 5 requests through a 2-slot speculative
    engine (perturbed draft -> per-slot mixed accept/reject every
    round), one of them submitted MID-FLIGHT while another slot is
    mid-generation, produce EXACTLY the tokens the non-speculative
    engine produces. (The paged-KV analog runs in
    test_paged_spec_crosses_pages_at_max_decode_len.)"""
    rng = np.random.RandomState(0)
    requests = [
        serving.Request(f"r{i}", list(rng.randint(0, 97, (4,))),
                        max_new_tokens=8)
        for i in range(4)
    ]
    late = serving.Request("late", list(rng.randint(0, 97, (4,))),
                           max_new_tokens=12)
    engine = _spec_engine(params, CFG, noisy_params, gamma=4)
    for req in requests:
        engine.submit(serving.Request(req.request_id, req.prompt,
                                      req.max_new_tokens))
    early = {}
    for _ in range(2):
        # Slots are mid-generation now; a draft that is accepted
        # whole finishes an 8-token request in these two rounds.
        early.update(engine.step())
    # Mid-flight admission: the free slot's target AND draft caches
    # prefill while the other slot keeps speculating.
    engine.submit(serving.Request(late.request_id, late.prompt,
                                  late.max_new_tokens))
    results = {**early, **_drain(engine)}
    assert set(results) == (
        {r.request_id for r in requests} | {"late"})
    for req in requests + [late]:
        want = reference_greedy(params, req.prompt,
                                req.max_new_tokens)
        assert results[req.request_id] == want, (
            req.request_id, results[req.request_id], want)
    stats = engine.spec_stats()
    # The perturbed draft must have produced BOTH accepts and rejects
    # (otherwise this test isn't exercising the ragged path).
    assert 0 < stats["accepted"] < stats["proposed"], stats


def test_hostile_draft_still_exact(params, dparams):
    """An unrelated random draft: near-zero acceptance, every round
    falls back to the target's correction token — output identical."""
    rng = np.random.RandomState(1)
    prompt = list(rng.randint(0, 97, (4,)))
    engine = _spec_engine(params, DCFG, dparams, gamma=3)
    engine.submit(serving.Request("h", prompt, max_new_tokens=8))
    results = _drain(engine)
    assert results["h"] == reference_greedy(params, prompt, 8)


def test_identical_draft_full_acceptance_and_midblock_stop(params):
    """Draft == target on ONE engine (slot reuse across sequential
    requests): (a) full acceptance — gamma+1 tokens commit per round,
    the bonus-token path; (b) an eos landing MID-BLOCK truncates the
    commit exactly like the non-speculative engine; (c) a
    max_new_tokens that is not a multiple of gamma+1 truncates the
    same way."""
    prompt = [5, 17, 31, 2]
    engine = _spec_engine(params, CFG, params, gamma=4, num_slots=1)
    engine.submit(serving.Request("f", prompt, max_new_tokens=12))
    results = _drain(engine)
    assert results["f"] == reference_greedy(params, prompt, 12)
    stats = engine.spec_stats()
    assert stats["accepted"] == stats["proposed"] > 0
    assert stats["acceptance_rate"] == 1.0
    # (b) eos at commit index 2: the first round commits 5 tokens, so
    # the stop happens mid-block and later committed tokens discard.
    prompt2 = [9, 9, 1, 42]
    full = reference_greedy(params, prompt2, 12)
    eos = full[2]
    want = full[:full.index(eos) + 1]
    engine.submit(serving.Request("e", prompt2, max_new_tokens=12,
                                  eos_id=eos))
    results = _drain(engine)
    assert results["e"] == want, (results["e"], want)
    # (c) truncation by max_new_tokens mid-block.
    engine.submit(serving.Request("t", prompt2, max_new_tokens=8))
    results = _drain(engine)
    assert results["t"] == reference_greedy(params, prompt2, 8)


def test_paged_spec_crosses_pages_at_max_decode_len(params,
                                                    noisy_params):
    """Paged + speculative at the boundary: prompt+max_new ==
    max_decode_len and verify blocks crossing page boundaries — the
    spec_window table margin routes tail writes to scratch; outputs
    stay exact and every page returns to the pool."""
    rng = np.random.RandomState(4)
    p1 = list(rng.randint(0, 97, (8,)))
    p2 = list(rng.randint(0, 97, (5,)))
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=32, kv_page_size=8,
        speculative=serving.SpeculativeConfig(CFG, noisy_params,
                                              gamma=4))
    engine.submit(serving.Request("b1", p1, max_new_tokens=24))
    engine.submit(serving.Request("b2", p2, max_new_tokens=20))
    results = _drain(engine)
    assert results["b1"] == reference_greedy(params, p1, 24,
                                             max_decode_len=32)
    assert results["b2"] == reference_greedy(params, p2, 20,
                                             max_decode_len=32)
    # All pages reclaimable after drain: free or parked unreferenced
    # in the prefix-cache LRU, each once (check()).
    engine.pages.check()
    occupancy = engine.occupancy()
    assert occupancy["kv_pages_in_use"] == 0
    assert (occupancy["kv_pages_free"]
            + occupancy["kv_pages_lru"]) == 8


def test_overcommit_preemption_with_speculation(params, noisy_params):
    """Overcommit + speculation: pool pressure preempts victims
    mid-speculative-decode; resumption re-prefills BOTH caches and
    the greedy continuation is unchanged."""
    rng = np.random.RandomState(5)
    reqs = [serving.Request(f"p{i}", list(rng.randint(0, 97, (6,))),
                            max_new_tokens=18) for i in range(4)]
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=32, kv_page_size=8,
        kv_num_pages=5, overcommit=True,
        speculative=serving.SpeculativeConfig(CFG, noisy_params,
                                              gamma=2))
    for r in reqs:
        engine.submit(r)
    results = _drain(engine, max_steps=800)
    assert set(results) == {r.request_id for r in reqs}
    assert engine.preemptions > 0, \
        "scenario failed to exercise preemption"
    for r in reqs:
        assert results[r.request_id] == reference_greedy(
            params, r.prompt, r.max_new_tokens,
            max_decode_len=32), r.request_id
    engine.pages.check()
    occupancy = engine.occupancy()
    assert occupancy["kv_pages_in_use"] == 0
    assert (occupancy["kv_pages_free"]
            + occupancy["kv_pages_lru"]) == 5


def test_speculative_rejects_bad_configs(params, dparams):
    with pytest.raises(ValueError, match="temperature"):
        _spec_engine(params, DCFG, dparams,
                     sampling=inf.SamplingConfig(temperature=0.7))
    with pytest.raises(ValueError, match="gamma"):
        _spec_engine(params, DCFG, dparams, gamma=0)
    import dataclasses
    paged_draft = dataclasses.replace(DCFG, kv_page_size=8)
    with pytest.raises(ValueError, match="kv_page_size"):
        _spec_engine(params, paged_draft, dparams)
    other_vocab = dataclasses.replace(DCFG, vocab_size=96)
    with pytest.raises(ValueError, match="vocab_size"):
        _spec_engine(params, other_vocab, dparams)


def test_frontend_exposes_acceptance_rate(params, noisy_params):
    """server.py plumbing: /v1/stats and /metrics carry the engine's
    speculative counters."""
    import urllib.request

    from batch_shipyard_tpu.models.server import ServingFrontEnd
    engine = _spec_engine(params, CFG, noisy_params, gamma=3)
    front = ServingFrontEnd(engine, port=0).start()
    try:
        front.generate({"prompt": [4, 8, 15], "max_new_tokens": 9})
        with urllib.request.urlopen(f"{front.url}/v1/stats",
                                    timeout=30) as resp:
            stats = json.loads(resp.read())
        spec = stats["speculative"]
        assert spec["gamma"] == 3
        assert spec["proposed"] > 0
        assert 0.0 <= spec["acceptance_rate"] <= 1.0
        with urllib.request.urlopen(f"{front.url}/metrics",
                                    timeout=30) as resp:
            text = resp.read().decode()
        assert "shipyard_serving_spec_acceptance_rate" in text
        assert "shipyard_serving_spec_proposed_tokens_total" in text
    finally:
        front.shutdown()


def test_paged_multitoken_insert_requires_spec_window(params):
    """Fail-fast guard (review finding): a multi-token insert into a
    paged cache WITHOUT a spec_window margin would clamp its tail
    table gather onto the slot's last live page — silent corruption.
    Only the serving engine (which sizes spec_window=gamma) may drive
    seq>1 paged inserts; everyone else must fail loudly."""
    import dataclasses
    cfg = dataclasses.replace(
        inf.decode_config(CFG, 32), kv_page_size=8, kv_num_pages=9)
    model = tfm.TransformerLM(cfg)
    cache = inf.empty_cache(model, 1)
    with pytest.raises(ValueError, match="spec_window"):
        model.apply({"params": params, "cache": cache},
                    jnp.zeros((1, 2), jnp.int32),
                    positions=jnp.zeros((1, 2), jnp.int32),
                    mutable=["cache"])

"""Cross-request prefix/KV-cache reuse (models/serving.py): greedy
token-equivalence of shared-prefix decode vs the cold-prefill
baseline (dense reference, paged, speculative, int8 page scales) and
page-refcount invariants under admit/preempt/finish churn."""

import dataclasses

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


@pytest.fixture(scope="module")
def params():
    model = tfm.TransformerLM(CFG)
    tokens = jnp.zeros((1, 8), jnp.int32)
    return model.init(jax.random.PRNGKey(7), tokens)["params"]


def reference_greedy(params, prompt, num_tokens):
    run, _model = inf.make_decoder(CFG, params, max_decode_len=64)
    tokens, _cache = run(jnp.asarray([prompt], jnp.int32), num_tokens,
                         jax.random.PRNGKey(0))
    return list(np.asarray(tokens[0, len(prompt):]))


def _drain(engine, steps=400):
    results = {}
    for _ in range(steps):
        for rid, toks in engine.step():
            results[rid] = toks
        if not engine.pending():
            break
    assert not engine.pending(), "engine failed to drain"
    return results


def _shared_prefix_requests(seed=0, base_pages=3, page=8, n=4):
    """One pilot request that publishes ``base_pages`` full pages,
    then n-1 followers sharing that prefix with distinct suffixes."""
    rng = np.random.RandomState(seed)
    base = list(rng.randint(0, 97, (base_pages * page,)))
    reqs = [serving.Request("pilot", base, max_new_tokens=5)]
    for i in range(n - 1):
        suffix = list(rng.randint(0, 97, (3 + 2 * i,)))
        reqs.append(serving.Request(f"fan{i}", base + suffix,
                                    max_new_tokens=4 + i))
    return reqs


def test_shared_prefix_matches_cold_baseline(params):
    """Requests hitting a cached 3-page prefix produce EXACTLY the
    tokens cold batch-1 greedy decoding produces — and the shared
    prefill path demonstrably ran."""
    reqs = _shared_prefix_requests()
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=64, kv_page_size=8)
    assert engine.prefix_cache
    for r in reqs:
        engine.submit(r)
    results = _drain(engine)
    assert engine.pages.stats()["hit_pages"] >= 3 * (len(reqs) - 1), \
        "followers did not reuse the pilot's pages"
    stats = engine.prefix_stats()
    assert stats["hit_rate"] > 0.5
    assert stats["published_pages"] >= 3
    for r in reqs:
        want = reference_greedy(params, r.prompt, r.max_new_tokens)
        assert results[r.request_id] == want, r.request_id
    engine.pages.check()


def test_prefix_cache_off_is_cold_path(params):
    """prefix_cache=False never matches, never publishes, and still
    produces the reference outputs — the control arm of the bench."""
    reqs = _shared_prefix_requests()
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=64, kv_page_size=8,
        prefix_cache=False)
    for r in reqs:
        engine.submit(r)
    results = _drain(engine)
    assert engine.pages.stats()["hit_pages"] == 0
    assert engine.pages.stats()["published_pages"] == 0
    assert engine.prefix_stats() is None
    for r in reqs:
        assert results[r.request_id] == reference_greedy(
            params, r.prompt, r.max_new_tokens), r.request_id


def test_shared_prefix_speculative_exact(params):
    """Speculative decoding over shared prefixes stays greedy-exact:
    the draft prefills the full prompt (its dense-cache invariant),
    only the target skips the cached pages."""
    reqs = _shared_prefix_requests(seed=2)
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=64, kv_page_size=8,
        speculative=serving.SpeculativeConfig(CFG, params, gamma=3))
    for r in reqs:
        engine.submit(r)
    results = _drain(engine)
    assert engine.pages.stats()["hit_pages"] > 0
    for r in reqs:
        assert results[r.request_id] == reference_greedy(
            params, r.prompt, r.max_new_tokens), r.request_id
    engine.pages.check()


def test_shared_prefix_int8_pages_identical_to_cold(params):
    """int8 page pool: the gathered prefix rows carry their stored
    scales verbatim, so shared-prefix outputs are byte-identical to
    the prefix-cache-off int8 engine at the same requests."""
    cfg = dataclasses.replace(CFG, kv_cache_dtype="int8")
    outs = {}
    for on in (True, False):
        engine = serving.ContinuousBatcher(
            cfg, params, num_slots=2, max_decode_len=64,
            kv_page_size=8, prefix_cache=on)
        for r in _shared_prefix_requests(seed=3):
            engine.submit(r)
        outs[on] = _drain(engine)
        if on:
            assert engine.pages.stats()["hit_pages"] > 0
    assert outs[True] == outs[False]


def test_refcount_invariants_under_churn(params):
    """Admit/preempt/finish churn on a deliberately tight overcommit
    pool with a shared prefix pinned across slots: after EVERY step,
    no page is freed while referenced, no page is double-owned, and
    the availability accounting balances; after drain, every page is
    reclaimable and no reference survives."""
    rng = np.random.RandomState(5)
    base = list(rng.randint(0, 97, (8,)))  # one shared page
    reqs = [serving.Request(
        f"c{i}", base + list(rng.randint(0, 97, (2 + i % 3,))),
        max_new_tokens=16) for i in range(6)]
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=32, kv_page_size=8,
        kv_num_pages=5, overcommit=True)
    for r in reqs:
        engine.submit(r)
    results = {}
    for step in range(600):
        for rid, toks in engine.step():
            results[rid] = toks
        engine.pages.check()
        if step == 5:
            # Mid-flight cancel: an active slot's pages (shared AND
            # owned) must release cleanly.
            engine.cancel("c5")
        if not engine.pending():
            break
    assert engine.preemptions > 0, \
        "scenario failed to exercise preemption"
    done = {r.request_id for r in reqs} - {"c5"}
    assert done <= set(results)
    for rid in done:
        req = next(r for r in reqs if r.request_id == rid)
        assert results[rid] == reference_greedy(
            params, req.prompt, req.max_new_tokens), rid
    occupancy = engine.occupancy()
    assert occupancy["kv_pages_in_use"] == 0
    assert (occupancy["kv_pages_free"] + occupancy["kv_pages_lru"]
            == occupancy["kv_pages_total"])


def test_lru_eviction_under_pool_pressure(params):
    """A full pool evicts unreferenced indexed pages (never pinned
    ones) to admit new work; the evicted prefix simply re-publishes
    on its next cold run."""
    rng = np.random.RandomState(6)
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=1, max_decode_len=32, kv_page_size=8,
        kv_num_pages=4)
    # Distinct 2-page prompts: each run parks 2 indexed pages; the
    # third admission must evict earlier LRU pages to reserve.
    for i in range(3):
        prompt = list(rng.randint(0, 97, (16,)))
        engine.submit(serving.Request(f"e{i}", prompt,
                                      max_new_tokens=4))
        results = _drain(engine)
        assert results[f"e{i}"] == reference_greedy(
            params, prompt, 4)
        engine.pages.check()
    assert engine.pages.stats()["evictions"] > 0


def test_prefix_cache_clear_and_rewarm(params):
    """prefix_cache_clear reclaims every unreferenced indexed page;
    the same prompt afterwards misses, recomputes, republishes, and
    still matches the reference."""
    rng = np.random.RandomState(7)
    base = list(rng.randint(0, 97, (16,)))
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=1, max_decode_len=64, kv_page_size=8)
    engine.submit(serving.Request("a", base + [3], max_new_tokens=3))
    _drain(engine)
    published = engine.pages.stats()["published_pages"]
    assert published >= 2
    cleared = engine.prefix_cache_clear()
    assert cleared >= 2
    assert engine.pages.stats()["indexed_pages"] == 0
    assert engine.occupancy()["prefix_index_pages"] == 0
    hits_before = engine.pages.stats()["hit_pages"]
    engine.submit(serving.Request("b", base + [9], max_new_tokens=3))
    results = _drain(engine)
    # cold again
    assert engine.pages.stats()["hit_pages"] == hits_before
    assert engine.pages.stats()["published_pages"] > published
    assert results["b"] == reference_greedy(params, base + [9], 3)
    engine.pages.check()


"""The page pool's allocation order, pinned to the page id: a fixed
schedule of 60 requests (four shared prefixes, exact-length twins,
cancels, one clear of the index) through a tiny CPU engine on a pool
too small for its slots, so admission waits, the LRU evicts and, under
overcommit, slots are preempted. The digests fold every block-table
row after every step, prefix_stats() and occupancy(); they were first
recorded on the commit BEFORE the pool left ContinuousBatcher for
models/kv_pages.py (PR 29's parent, 4d9c6dd), which PR 29
reproduced. Which page a request
is handed decides which prefix pages survive under pressure, so a
change of the pool that moves a digest has changed the hit rate. The
served tokens are not part of it (no request has an eos, so nothing
the books see depends on a token's value).

PR 30 moved the digests BY DESIGN and they were recorded anew, once,
on its finished tree: the engine keeps one decode step in flight, so
a request's last token is read back, and its pages come back, in the
step() call AFTER the one that dispatched it (the table rows after
that call still hold them), its successor is seated a call later, and
occupancy() counts the token in flight. Under pressure that shifts
which pages the LRU has to give when. What the lookahead must NOT
shift is held beside the digests, on numbers recorded on PR 30's
parent (72efe5f): with room for every page (no eviction, no wait, no
cancel) the index's lookups, hits and published pages are a function
of the order of admission alone, which is the queue's; and the pool's
books balance (pages.check()) after every step of every run.

PR 34 moved them again BY DESIGN, recorded once on its finished tree:
an admission no longer lands the step in flight before its prefill,
so in a call that admits, the request that step finishes gives its
pages and its slot back AFTER the call's admissions instead of
between them (as a call that admits nobody has done since PR 30), and
a request that its first token ends leaves at that token's landing,
behind the call's decode dispatch. The numbers without pressure held
as they were.

PR 44 did NOT move them: occupancy()'s kv_blocks_attended no longer
counts a block for an idle slot (the decode kernel is handed length 0
for it), and the digests fold that key with the idle term put back
(_as_recorded), so that they stay PR 34's and go on saying what they
are for: the pool hands out the same pages in the same order.

PR 48 did NOT move them either: occupancy() gained
kv_first_chunks_prefetched (seated slots less one: how often the
decode kernel's hand-over engages), a key the digests were recorded
without and fold without (_as_recorded)."""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.models import serving
from batch_shipyard_tpu.models import transformer as tfm

CFG = tfm.TransformerConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=2, d_head=16,
    d_ff=64, max_seq_len=64, dtype=jnp.float32,
    param_dtype=jnp.float32)
PAGE = 8


def _table(engine) -> np.ndarray:
    return engine.pages.table


def _schedule(seed: int = 11) -> list[serving.Request]:
    rng = np.random.RandomState(seed)
    bases = [list(rng.randint(1, 97, (pages * PAGE,)))
             for pages in (2, 3, 1, 2)]
    reqs = []
    for i in range(60):
        kind = i % 10
        if kind == 7:       # no shared prefix at all
            prompt = list(rng.randint(1, 97, (int(rng.randint(3, 30)),)))
        elif kind in (3, 4):    # exact-length twins, admitted together
            prompt = bases[(i // 10) % 4] + list(
                np.random.RandomState(i // 10).randint(1, 97, (PAGE,)))
        else:
            prompt = bases[int(rng.randint(0, 4))] + list(
                rng.randint(1, 97, (int(rng.randint(1, 14)),)))
        reqs.append(serving.Request(
            f"g{i}", [int(t) for t in prompt],
            max_new_tokens=int(rng.randint(1, 13))))
    return reqs


def _as_recorded(occupancy: dict) -> dict:
    """occupancy() as the digests were recorded: kv_blocks_attended
    with one block for each idle slot, which it counted until PR 44,
    and without kv_first_chunks_prefetched, a key since PR 48."""
    recorded = dict(occupancy, kv_blocks_attended=(
        occupancy["kv_blocks_attended"] + occupancy["slots_total"]
        - occupancy["slots_active"]))
    assert recorded.pop("kv_first_chunks_prefetched") == max(
        occupancy["slots_active"] - 1, 0)
    return recorded


def _stats_as_recorded(engine):
    """prefix_stats() as the digests were recorded: without the bytes
    a page and a token hold, keys since PR 50 (read off the cache's
    leaves: inference.pool_page_bytes)."""
    from batch_shipyard_tpu.models import inference as inf
    if engine.prefix_stats() is None:       # the prefix cache is off
        return None
    recorded = dict(engine.prefix_stats())
    assert recorded.pop("page_bytes") == inf.pool_page_bytes(
        engine.cache) > 0
    assert recorded.pop("bytes_per_token") * engine.page_size == \
        engine.pages.page_bytes
    return recorded


def run_schedule(engine, press: bool = True) -> tuple[str, dict]:
    """Drive the schedule, folding the books into one digest after
    every step; returns it with the final counters (for a failure's
    message). ``press`` False leaves out the cancels and the clear,
    whose victims depend on what is seated at that very step."""
    digest = hashlib.sha256()
    reqs = _schedule()
    finished = []
    for step in range(2000):
        if step % 4 == 0 and reqs:
            for req in reqs[:5]:
                engine.submit(req)
            del reqs[:5]
        if press and step in (9, 23, 37):
            active = engine.active_request_ids()
            if active:
                engine.cancel(sorted(active)[0])
        if press and step == 30:
            engine.prefix_cache_clear()
        finished += [rid for rid, _ in engine.step()]
        engine.pages.check()
        digest.update(_table(engine).tobytes())
        digest.update(json.dumps(
            [_stats_as_recorded(engine),
             _as_recorded(engine.occupancy()),
             engine.preemptions], sort_keys=True).encode())
        if not reqs and not engine.pending():
            break
    assert not engine.pending(), "engine failed to drain"
    return digest.hexdigest(), {
        "finished": len(finished), "prefix": engine.prefix_stats(),
        "occupancy": engine.occupancy(),
        "preemptions": engine.preemptions}


# Recorded on PR 34's finished tree (see the module docstring).
GOLDEN = {
    "reservation": (
        "e4c050965c9ffb8712c4b02c505fd359"
        "0049c6b96fb543ee5d982448b6f392fd",
        dict(kv_num_pages=13)),
    "overcommit": (
        "26f4e143420ed06802f3e8aa4bae4282"
        "b6b2363434db472f9fc3efa7fe43097a",
        dict(kv_num_pages=9, overcommit=True)),
    "reservation-no-prefix-cache": (
        "a8b6b42a89bf659bf24fa0d8a519135e"
        "a766f51ed3fe4767658dd28ab22edcf1",
        dict(kv_num_pages=13, prefix_cache=False)),
    "overcommit-no-prefix-cache": (
        "a70b330f1caa3a51c247e9cf8a83fcbd"
        "9ea42a11e78e91f5915201467d6ca628",
        dict(kv_num_pages=9, overcommit=True, prefix_cache=False)),
}


def _engine(**kwargs):
    model = tfm.TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(7),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return serving.ContinuousBatcher(
        CFG, params, num_slots=3, max_decode_len=64,
        kv_page_size=PAGE, **kwargs)


# prefix_stats() after the 60 requests on a pool of 400 pages, on PR
# 30's parent (72efe5f), under either policy.
NO_PRESSURE = {"lookups": 60, "hit_pages": 95, "hit_tokens": 760,
               "total_prompt_tokens": 1299, "published_pages": 41,
               "indexed_pages": 41, "evictions": 0}


@pytest.mark.parametrize("overcommit", [False, True])
def test_without_pressure_the_hits_are_the_parents(overcommit):
    engine = _engine(kv_num_pages=400, overcommit=overcommit)
    _digest, final = run_schedule(engine, press=False)
    assert final["finished"] == 60 and final["preemptions"] == 0
    assert {key: final["prefix"][key] for key in NO_PRESSURE} == \
        NO_PRESSURE


@pytest.mark.parametrize("policy", sorted(GOLDEN))
def test_allocation_order_is_the_parents(policy):
    want, kwargs = GOLDEN[policy]
    engine = _engine(**kwargs)
    got, final = run_schedule(engine)
    # The schedule is worth pinning only while it presses the pool.
    if final["prefix"] is not None:
        assert final["prefix"]["evictions"] > 0, final
        assert final["prefix"]["hit_pages"] > 0, final
    if kwargs.get("overcommit"):
        assert final["preemptions"] > 0, final
    assert got == want, final

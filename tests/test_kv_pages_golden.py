"""The page pool's allocation order, pinned to the page id: a fixed
schedule of 60 requests (four shared prefixes, exact-length twins,
cancels, one clear of the index) through a tiny CPU engine on a pool
too small for its slots, so admission waits, the LRU evicts and, under
overcommit, slots are preempted. The digests below were recorded on
the commit BEFORE the pool left ContinuousBatcher for
models/kv_pages.py (PR 29's parent, 4d9c6dd): every block-table row
after every step, prefix_stats() and occupancy(). Which page a request
is handed decides which prefix pages survive under pressure, so a
change of the pool that moves a digest has changed the hit rate. The
served tokens are not part of it (no request has an eos, so nothing
the books see depends on a token's value)."""

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


def run_schedule(engine) -> tuple[str, dict]:
    """Drive the schedule, folding the books into one digest after
    every step; returns it with the final counters (for a failure's
    message)."""
    digest = hashlib.sha256()
    reqs = _schedule()
    finished = []
    for step in range(2000):
        if step % 4 == 0 and reqs:
            for req in reqs[:5]:
                engine.submit(req)
            del reqs[:5]
        if step in (9, 23, 37):
            active = engine.active_request_ids()
            if active:
                engine.cancel(sorted(active)[0])
        if step == 30:
            engine.prefix_cache_clear()
        finished += [rid for rid, _ in engine.step()]
        digest.update(_table(engine).tobytes())
        digest.update(json.dumps(
            [engine.prefix_stats(), engine.occupancy(),
             engine.preemptions], sort_keys=True).encode())
        if not reqs and not engine.pending():
            break
    assert not engine.pending(), "engine failed to drain"
    return digest.hexdigest(), {
        "finished": len(finished), "prefix": engine.prefix_stats(),
        "occupancy": engine.occupancy(),
        "preemptions": engine.preemptions}


# Recorded on the parent commit (see the module docstring).
GOLDEN = {
    "reservation": (
        "485d77cdf94e9d8018a529c9c756a9c9"
        "f3a2874ae273ce1076fce2775a8e706b",
        dict(kv_num_pages=13)),
    "overcommit": (
        "c258a3e3cf965c4913bde01f50934d63"
        "b98bcbe3c248b7a7ee975f003bbe1892",
        dict(kv_num_pages=9, overcommit=True)),
    "reservation-no-prefix-cache": (
        "b62d6a0f9202447977d41f690026ec49"
        "3ef8256ce7991f6b5c968248602c6d0a",
        dict(kv_num_pages=13, prefix_cache=False)),
    "overcommit-no-prefix-cache": (
        "471671c38f76c5e7e94d6541c1688a1a"
        "4775e9da00f2df40edabe5161ae3b47b",
        dict(kv_num_pages=9, overcommit=True, prefix_cache=False)),
}


@pytest.mark.parametrize("policy", sorted(GOLDEN))
def test_allocation_order_is_the_parents(policy):
    want, kwargs = GOLDEN[policy]
    model = tfm.TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(7),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=3, max_decode_len=64,
        kv_page_size=PAGE, **kwargs)
    got, final = run_schedule(engine)
    # The schedule is worth pinning only while it presses the pool.
    if final["prefix"] is not None:
        assert final["prefix"]["evictions"] > 0, final
        assert final["prefix"]["hit_pages"] > 0, final
    if kwargs.get("overcommit"):
        assert final["preemptions"] > 0, final
    assert got == want, final

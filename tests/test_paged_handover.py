"""The paged decode kernel's hand-over between programs (interpret
mode): while a seated slot attends its last chunk of pages, the first
chunk of the NEXT seated slot is already in flight into the free
buffer half; that slot's program starts nothing and waits for it; a
parked program between them passes the fetch on. Outputs are bit for
bit what each slot gives alone."""

import numpy as np
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

from batch_shipyard_tpu.ops import paged_attention as pa

# ---- the hand-over between programs: a seated slot's first chunk is
# ---- fetched behind the last chunk of the seated slot before it

def _seated_case(rng, dtype, heads, kv_heads, lengths, depth=64, page=8,
                 entries=24, positions=1, pool=None):
    """A pool (of ``pool`` pages; by default as many as are held, and
    page 0) in which each slot holds exactly the pages its length
    reaches (a ring that has wrapped: all its entries), its table's
    dead tail pointing at page 0 as a freed slot's does."""
    need = [min(entries, -(-length // page)) for length in lengths]
    pool = pool or 1 + sum(need)
    q = jnp.asarray(rng.randn(len(lengths), positions, heads, depth),
                    dtype)
    pools = [jnp.asarray(
        rng.randn(pool, page, kv_heads * depth), dtype)
        for _ in range(2)]
    table = np.zeros((len(lengths), entries), np.int32)
    ids = iter(rng.permutation(pool - 1) + 1)
    for b, pages in enumerate(need):
        table[b, :pages] = [next(ids) for _ in range(pages)]
    return q, pools[0], pools[1], jnp.asarray(table)


# road -> how the call is made. The small ones: float32, pages of 8,
# 14 query over 2 K/V heads of 64 (an MHA pool: 4 over 4); a table of
# 24 entries walks 8 pages = 64 keys a chunk, a ring of 4 entries is
# one chunk whatever the length (a window layer's whole visit, as in
# K-EXAONE's five window layers of six). The served ones, bfloat16 as
# on the chip: Baichuan's MHA pool through the dispatch (the unnamed
# call, 2 pages = 128 keys a chunk), K-EXAONE's two query positions
# over a full table (8 pages = 512 keys a chunk) and over its ring of
# 4 pages under a window of 128, SDAR's block of four positions that
# all see all keys, and its two blocks of a block pass (block-causal
# between them; every other seated slot's second block dead).
_SMALL_ROAD = dict(dtype=jnp.float32, heads=14, kv_heads=2, tol=2e-6,
                   pool=1 + 8 * 24)
_SERVED_ROAD = dict(dtype=jnp.bfloat16, depth=128, page=64, tol=3e-2)
_HANDOVER_ROADS = {
    "grouped": dict(_SMALL_ROAD),
    "mha": dict(_SMALL_ROAD, heads=4, kv_heads=4),
    "window": dict(_SMALL_ROAD, window=100),
    "ring": dict(_SMALL_ROAD, window=20, entries=4),
    "verify": dict(_SMALL_ROAD, positions=2),
    "verify-ring": dict(_SMALL_ROAD, positions=2, window=16, entries=4),
    "block": dict(_SMALL_ROAD, positions=4, block=4),
    "two-blocks": dict(_SMALL_ROAD, heads=4, positions=8, block=4),
    "two-blocks-some-dead": dict(_SMALL_ROAD, heads=4, positions=8,
                                 block=4, live=True),
    "baichuan": dict(_SERVED_ROAD, heads=32, kv_heads=32, entries=32),
    "kexaone-full": dict(_SERVED_ROAD, heads=64, kv_heads=8,
                         entries=128, positions=2),
    "kexaone-ring": dict(_SERVED_ROAD, heads=64, kv_heads=8, entries=4,
                         positions=2, window=128),
    "sdar": dict(_SERVED_ROAD, heads=32, kv_heads=4, entries=129,
                 positions=4, block=4),
    "sdar-two-blocks": dict(_SERVED_ROAD, heads=32, kv_heads=4,
                            entries=129, positions=8, block=4, live=True),
}
# arrangement -> lengths of EIGHT slots (one compiled program a road
# for all of them), in keys at 64 a chunk (the served roads scale them
# to their own chunk): who starts a first chunk, who passes one on,
# and which buffer half it lands in. Chunks a slot: 5 -> 1, 64 -> 1,
# 65 -> 2, 130 -> 3, 192 -> 3.
_ARRANGEMENTS = {
    "first-parked": [0, 70, 5, 130, 9, 64, 65, 33],
    "last-parked": [70, 5, 130, 9, 64, 65, 33, 0],
    "runs-parked": [5, 0, 0, 130, 0, 0, 0, 70],
    "one-seated": [0, 0, 0, 0, 0, 130, 0, 0],
    "none-seated": [0, 0, 0, 0, 0, 0, 0, 0],
    # neighbours of odd and even chunk counts: the half flips or not
    "odd-even": [70, 130, 131, 66, 5, 6, 192, 64],
    "one-then-several": [5, 192, 64, 65, 1, 129, 0, 128],
    "all-one-chunk": [9, 17, 33, 64, 1, 2, 63, 40],
}
_HANDOVER_CASES = (
    [("grouped", name) for name in sorted(_ARRANGEMENTS)] +
    [(road, name) for road in ("mha", "window", "ring", "verify",
                               "verify-ring", "block", "two-blocks",
                               "two-blocks-some-dead")
     for name in ("first-parked", "runs-parked", "odd-even")] +
    [(road, "runs-parked") for road in ("baichuan", "kexaone-full",
                                        "kexaone-ring", "sdar",
                                        "sdar-two-blocks")] +
    [("baichuan", "odd-even"), ("kexaone-ring", "last-parked")])


@pytest.mark.parametrize("road,arrangement", _HANDOVER_CASES,
                         ids=["-".join(case) for case in _HANDOVER_CASES])
def test_the_next_seated_slots_first_chunk_is_handed_over(
        road, arrangement, capsys):
    """Every seated slot but the first finds its chunk 0 already
    started by the seated slot before it; a parked program between
    them passes the fetch on. The call's rows are BIT FOR BIT what
    each seated slot gives alone (a batch of one: the first seated
    slot of its call, which starts its own chunk 0 as every slot did
    before the hand-over), zeros for the parked ones, and the XLA
    gather's within the rounding. The call runs with every DMA
    executed AT ITS START (the interpreter's "eager" mode, the
    earliest a copy can land: one into a buffer half still being read
    would change a row) and the single slots with every DMA executed
    at its wait (the latest); a copy left in flight at the kernel's
    end leaves its semaphore above zero, which the interpreter
    reports."""
    spec = dict(_HANDOVER_ROADS[road])
    tol, window = spec.pop("tol"), spec.pop("window", 0)
    block, some_dead = spec.pop("block", 0), spec.pop("live", False)
    positions = spec.get("positions", 1)
    page = spec.get("page", 8)
    entries = spec.get("entries", 24)
    chunk = pa.gqa_chunk_pages(
        page, spec["kv_heads"] * spec.get("depth", 64),
        jnp.dtype(spec["dtype"]).itemsize, entries)
    # the arrangement's lengths at this road's keys a chunk, and no
    # seated slot shorter than its query positions
    scale = chunk * page / 64
    lengths = [0 if n == 0 else max(positions, int(n * scale))
               for n in _ARRANGEMENTS[arrangement]]
    rng = np.random.RandomState(len(road) + len(arrangement))
    q, k_pages, v_pages, table = _seated_case(
        rng, lengths=lengths, **spec)
    lengths = jnp.asarray(lengths, jnp.int32)
    # every other slot's second block dead (a block pass's plain slot)
    live = jnp.asarray([positions // (1 + b % 2)
                        for b in range(len(lengths))], jnp.int32) \
        if some_dead else None
    if road == "baichuan":
        def call(*args, at=None):   # the unnamed call, by the dispatch
            return pa.paged_decode_attention(*args, impl="kernel")
    else:
        def call(*args, at=slice(None)):
            return pa.gqa_paged_decode_attention_kernel(
                *args, window=window, block=block,
                live_positions=None if live is None else live[at])

    assert list(np.asarray(pa.next_seated(lengths))) == [
        min([j for j in range(b + 1, len(lengths)) if lengths[j] > 0],
            default=len(lengths)) for b in range(len(lengths))]
    with pltpu.force_tpu_interpret_mode(
            pltpu.InterpretParams(dma_execution_mode="eager")):
        got = np.asarray(call(q, k_pages, v_pages, table, lengths),
                         np.float32)
    assert "non-zero count" not in capsys.readouterr().out
    want = np.asarray(pa.paged_decode_attention_xla_windowed(
        q, k_pages, v_pages, table, lengths, window=window,
        block=block, live_positions=live), np.float32)
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    seated = np.flatnonzero(np.asarray(lengths) > 0)
    assert not np.delete(got, seated, axis=0).any()
    with pltpu.force_tpu_interpret_mode():
        for b in seated:
            alone = call(q[b:b + 1], k_pages, v_pages, table[b:b + 1],
                         lengths[b:b + 1], at=slice(b, b + 1))
            np.testing.assert_array_equal(
                got[b], np.asarray(alone, np.float32)[0])

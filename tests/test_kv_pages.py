"""The KV page pool alone (models/kv_pages.py), with no engine and no
JAX: seeded schedules of seat / publish / grow / release /
clear_unreferenced under both admission policies with check() after
every call, and the edges one by one: a seat that must wait touches
no book, a pinned page is never evicted, an exact-length twin keeps
its copy private, a dry pool under overcommit raises PoolDry with the
books as they were."""

import copy
import subprocess
import sys

import numpy as np
import pytest

from batch_shipyard_tpu.models import kv_pages

PAGE = 4


def _pool(num_pages, num_slots=3, max_decode_len=32, **kwargs):
    return kv_pages.PagePool(num_slots, num_pages, PAGE,
                             max_decode_len, **kwargs)


def _books(pool) -> dict:
    """A deep copy of every field, arrays as lists (comparable)."""
    return {name: (value.tolist() if isinstance(value, np.ndarray)
                   else copy.deepcopy(value))
            for name, value in vars(pool).items()}


def test_imports_nothing_of_jax_or_the_engine():
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "from batch_shipyard_tpu.models import kv_pages\n"
         "print(sorted(m for m in sys.modules if m == 'jax' or "
         "m.startswith('jax.') or m.endswith('.serving')))"],
        capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "[]"


class _Driver:
    """The least an engine does around the pool: a queue served from
    its head into free slots, one token a step for every seated
    request (a span of them when speculative), the victim with the
    fewest generated tokens when the pool runs dry. It also keeps
    what every indexed page was written with, so that a seat which
    matches pages can be held to their CONTENT."""

    def __init__(self, pool, rng, span=0):
        self.pool, self.rng, self.span = pool, rng, span
        self.slots = [None] * pool.num_slots
        self.queue = []
        self.written = {}       # page id -> the prompt prefix it ends
        self.preemptions = 0
        self.waits = 0

    def call(self, fn, *args):
        out = fn(*args)
        self.pool.check()
        return out

    def admit(self):
        for i, held in enumerate(self.slots):
            if held is not None or not self.queue:
                continue
            prompt, max_new, generated = self.queue[0]
            tokens = prompt + [7] * generated
            before = _books(self.pool)
            seat = self.call(self.pool.seat, i, tokens,
                             max_new - generated)
            if seat is None:
                assert _books(self.pool) == before
                self.waits += 1
                break
            self.queue.pop(0)
            blocks = -(-len(tokens) // PAGE)
            row = self.pool.table[i]
            assert (row == seat.row).all()
            assert (row[:blocks] != self.pool.scratch_page).all()
            assert (row[blocks:] == self.pool.scratch_page).all()
            for b in range(seat.matched):
                assert self.written[int(row[b])] == \
                    tokens[:(b + 1) * PAGE], "matched a foreign page"
            if seat.matched:
                assert seat.prefix_len == seat.matched * PAGE
                assert list(seat.prefix_ids[:seat.matched]) == \
                    list(row[:seat.matched])
                assert list(seat.suffix_row[:blocks - seat.matched]) \
                    == list(row[seat.matched:blocks])
            for b in range(seat.matched, len(tokens) // PAGE):
                self.written[int(row[b])] = tokens[:(b + 1) * PAGE]
            self.call(self.pool.publish, i, seat)
            self.slots[i] = [prompt, max_new, generated + 1]

    def preempt(self, exclude):
        victims = [j for j, held in enumerate(self.slots)
                   if held is not None and j != exclude]
        victim = min(victims, key=lambda j: self.slots[j][2])
        self.queue.insert(0, tuple(self.slots[victim]))
        self.slots[victim] = None
        self.call(self.pool.release, victim)
        self.preemptions += 1

    def step(self):
        self.admit()
        for i in range(len(self.slots)):
            held = self.slots[i]
            if held is None:
                continue
            prompt, max_new, generated = held
            if generated >= max_new:
                self.slots[i] = None
                self.call(self.pool.release, i)
                assert (self.pool.table[i]
                        == self.pool.scratch_page).all()
                continue
            position = len(prompt) + generated - 1
            while True:
                try:
                    self.call(self.pool.grow, i, position, self.span,
                              len(prompt) + max_new)
                    break
                except kv_pages.PoolDry:
                    self.pool.check()
                    self.preempt(exclude=i)
            assert self.pool.table[i, position // PAGE] != \
                self.pool.scratch_page
            held[2] += 1 + int(self.rng.randint(0, self.span + 1))
            held[2] = min(held[2], max_new)


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("prefix_cache", [True, False],
                         ids=["cache", "nocache"])
@pytest.mark.parametrize("policy", ["reservation", "overcommit"])
def test_seeded_schedule_keeps_the_books(policy, prefix_cache, seed):
    rng = np.random.RandomState(seed)
    overcommit = policy == "overcommit"
    pool = _pool(7 if overcommit else 10, overcommit=overcommit,
                 prefix_cache=prefix_cache,
                 spec_window=2 if seed == 2 else 0)
    driver = _Driver(pool, rng, span=2 if seed == 2 else 0)
    bases = [list(rng.randint(1, 50, (pages * PAGE,)))
             for pages in (1, 2, 3)]
    for step in range(400):
        if step < 120 and step % 2 == 0:
            prompt = bases[int(rng.randint(0, 3))] + list(
                rng.randint(1, 50, (int(rng.randint(0, 6)),)))
            prompt = prompt or [1]
            driver.queue.append(
                ([int(t) for t in prompt], int(rng.randint(1, 9)), 0))
        if step % 37 == 36:
            driver.call(pool.clear_unreferenced)
        if step % 29 == 28:     # a cancel
            seated = [i for i, held in enumerate(driver.slots)
                      if held is not None]
            if seated:
                driver.slots[seated[0]] = None
                driver.call(pool.release, seated[0])
        driver.step()
        if step >= 120 and not driver.queue and \
                not any(driver.slots):
            break
    assert not driver.queue and not any(driver.slots), "did not drain"
    assert driver.waits > 0, "the pool was never short"
    if overcommit:
        assert driver.preemptions > 0
    occupancy = pool.occupancy([])
    assert occupancy["kv_pages_in_use"] == 0
    assert occupancy["kv_pages_free"] + occupancy["kv_pages_lru"] \
        == occupancy["kv_pages_total"] == pool.num_pages
    assert occupancy["kv_blocks_attended"] == 0
    stats = pool.stats()
    if prefix_cache:
        assert stats["hit_pages"] > 0 and stats["evictions"] > 0
        assert stats["hit_tokens"] == stats["hit_pages"] * PAGE
    else:
        assert not any(stats.values())
    assert pool.clear_unreferenced() == occupancy["kv_pages_lru"]
    pool.check()
    assert pool.occupancy([])["kv_pages_free"] == pool.num_pages


def test_reservation_exhaustion_waits_and_touches_no_book():
    pool = _pool(6)
    prompt = list(range(1, 10))             # 3 pages, worst case 4
    assert pool.seat(0, prompt, 6) is not None
    before = _books(pool)
    assert pool.seat(1, [9] * 9, 6) is None     # worst 4 > the 2 left
    assert _books(pool) == before
    pool.check()
    seat = pool.seat(1, prompt[:4] + [3], 3)    # worst 2: fits
    assert seat is not None and seat.matched == 0   # not published yet
    pool.check()
    # Growth inside a reservation can never run dry.
    for position in range(9, 15):
        pool.grow(0, position, 0, 15)
        pool.check()
    assert pool.occupancy([15, 5])["kv_pages_free"] == 0
    with pytest.raises(RuntimeError, match="exhausted mid-decode"):
        pool.grow(1, 8, 0, 16)      # past what slot 1 reserved


def test_pinned_page_is_never_evicted():
    pool = _pool(4, num_slots=2)
    shared = list(range(1, 9))              # two full pages
    seat = pool.seat(0, shared + [9], 3)
    pool.publish(0, seat)
    pinned = [int(p) for p in seat.row[:2]]
    pool.release(0)                         # both park in the LRU
    reader = pool.seat(0, shared + [5], 3)
    assert reader.matched == 2
    assert [int(p) for p in reader.row[:2]] == pinned
    pool.check()
    # One page is free; a second request's worst case is two, and
    # the only other pages are pinned: it waits, nothing is evicted.
    assert pool.seat(1, [40, 41, 42, 43, 44], 3) is None
    assert pool.stats()["evictions"] == 0
    assert pool.clear_unreferenced() == 0
    assert [int(p) for p in pool.table[0, :2]] == pinned
    pool.release(0)
    assert pool.seat(1, [40, 41, 42, 43, 44], 3) is not None
    assert pool.stats()["lru_pages"] == 2   # free pages went first
    pool.check()


def test_exact_length_twin_keeps_its_copy_private():
    pool = _pool(8, num_slots=2)
    prompt = list(range(1, 9))              # exactly two pages
    first = pool.seat(0, prompt, 2)
    twin = pool.seat(1, prompt, 2)          # before either publishes
    assert first.matched == twin.matched == 0
    pool.publish(0, first)
    pool.publish(1, twin)
    pool.check()
    stats = pool.stats()
    assert stats["published_pages"] == 2 and stats["indexed_pages"] == 2
    assert set(first.row[:2]).isdisjoint(twin.row[:2])
    pool.release(1)     # the twin's stayed OWNED: straight back
    assert pool.stats()["lru_pages"] == 0
    assert pool.occupancy([10])["kv_pages_free"] == 6
    # A later request matches one page only: the cap leaves a suffix
    # token to run the forward on.
    later = pool.seat(1, prompt, 2)
    assert later.matched == 1 and later.row[0] == first.row[0]
    pool.check()


def test_dry_pool_under_overcommit_raises_with_books_as_they_were():
    pool = _pool(3, num_slots=2, overcommit=True, prefix_cache=False)
    assert pool.seat(0, [1, 2, 3], 9) is not None   # 1 page + headroom
    assert pool.seat(1, [4, 5, 6], 13) is not None
    assert pool.grow(0, 4, 0, 12)               # the last free page
    assert not pool.grow(0, 5, 0, 12)           # already covered
    before = _books(pool)
    with pytest.raises(kv_pages.PoolDry):
        pool.grow(1, 4, 0, 16)
    assert _books(pool) == before
    pool.check()
    pool.release(0)                             # the engine's victim
    assert pool.grow(1, 4, 0, 16)
    pool.check()


def test_span_that_runs_dry_half_way_keeps_what_it_appended():
    """"Books as they were" is the FAILING allocation's: a speculative
    verify block that wants two pages and finds one keeps that one,
    and the call after the engine's preemption asks for the rest."""
    pool = _pool(4, num_slots=2, overcommit=True, prefix_cache=False)
    assert pool.seat(0, [1, 2, 3], 9) is not None
    assert pool.seat(1, [4, 5, 6, 7, 8], 11) is not None    # 2 pages
    assert len(pool._free_pages) == 1
    with pytest.raises(kv_pages.PoolDry):
        pool.grow(1, 8, 7, 16)                  # blocks 2 and 3
    pool.check()
    got = int(pool.table[1, 2])
    assert got != pool.scratch_page
    assert pool.table[1, 3] == pool.scratch_page
    assert pool._slot_pages[1][-1] == got and not pool._free_pages
    half = _books(pool)
    with pytest.raises(kv_pages.PoolDry):       # nothing more to lose
        pool.grow(1, 8, 7, 16)
    assert _books(pool) == half
    pool.release(0)
    assert pool.grow(1, 8, 7, 16)               # block 3 alone
    assert pool.table[1, 2] == got
    assert pool.table[1, 3] != pool.scratch_page
    assert not pool.grow(1, 8, 7, 16)
    pool.check()


def _break_two_states(pool):
    pool._release_pages([int(pool.table[0, 2])])    # OWNED and FREE


def _break_refcount(pool):
    pool._page_ref[int(pool.table[0, 0])] += 1      # a reader too many


def _break_avail(pool):
    pool._avail_pages += 1


def _break_table(pool):
    pool.table[1, 0] = pool.table[0, 2]     # a row naming a foreign page


@pytest.mark.parametrize("damage,says", [
    (_break_two_states, "two lifecycle states"),
    (_break_refcount, "refcounts out of sync"),
    (_break_avail, "_avail_pages"),
    (_break_table, "table row"),
])
def test_check_names_a_broken_book(damage, says):
    pool = _pool(8, num_slots=2)
    seat = pool.seat(0, list(range(1, 11)), 4)  # two full pages + tail
    pool.publish(0, seat)
    pool.check()
    damage(pool)
    with pytest.raises(AssertionError, match=says):
        pool.check()

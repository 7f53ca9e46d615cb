"""The paged KV cache's books, host side: which physical page of the
pool ([P, page, Hkv*D] in every layer that attends over its whole
context, transformer._decode_attend_paged) belongs to which slot, what
admission may still promise, and which full prompt pages a later
request can share. A layer with a sliding window keeps no page of the
pool: its newest keys sit in a ring of the slot's own
(transformer.Attention._decode_attend_ring), fixed at
ceil(window / page) + 1 pages a slot, which needs no allocation, no
reservation and no table on the host; ring_occupancy below is all
there is to account. The serving engine
(models/serving.py) schedules and runs the device; this module
accounts, and imports nothing of JAX or of the engine. ``table`` is
the block table's host copy, which the engine pushes to the device.

Page lifecycle: FREE (_free_pages) -> OWNED (a slot's private
_slot_pages) -> PINNED (indexed, refcount >= 1, read through
_slot_shared) -> LRU (indexed, refcount 0, evictable) -> FREE.
Invariant (check()): _avail_pages = total - pinned -
sum(_slot_reserved). LRU pages count as available because
_alloc_page can always evict them; pinned pages cannot be reclaimed.

A pool smaller than num_slots * max_blocks admits by RESERVATION
(default) of a request's worst-case page count, so growth during
decode can never deadlock two half-grown slots against each other, or
by OVERCOMMIT: the prompt's pages plus one of headroom, and a grow()
that finds the pool dry raises PoolDry for the engine to preempt.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import Optional

import numpy as np


def ring_occupancy(held_tokens: list[int], num_slots: int,
                   page_size: int, ring_pages: int,
                   window: int) -> dict:
    """The window layers' page group, as ContinuousBatcher.occupancy
    reports it beside the pool's: a slot's ring never holds more than
    ``ring_pages`` pages whatever its length (the bound is the
    ring's size, not a policy), so window_pages_in_use is the pages a
    seated request's keys reach, min(ceil(tokens / page), ring_pages)
    each, of window_pages_total = num_slots * ring_pages. Beside
    them the keys ONE full and ONE window layer attend in the decode
    step dispatched from this state: every held token, and each
    slot's newest ``window`` at most."""
    return {
        "window_pages_in_use": sum(
            min(-(-tokens // page_size), ring_pages)
            for tokens in held_tokens),
        "window_pages_total": num_slots * ring_pages,
        "kv_tokens_full": sum(held_tokens),
        "kv_tokens_window": sum(min(tokens, window)
                                for tokens in held_tokens)}


class PoolDry(Exception):
    """Overcommit only: an allocation in grow() found no page free or
    evictable and touched no book (pages the call appended before it
    stay). The engine release()s a victim and asks again."""


@dataclasses.dataclass
class Seat:
    """What a seated request's prefill call needs: the slot's table
    row, whose first ``matched`` pages came pinned out of the index.
    The shared prefill (matched > 0) also takes the matched ids, the
    fresh pages alone as a row, the prefix's tokens; publish(), keys."""
    matched: int
    row: np.ndarray
    keys: list[bytes]
    prefix_ids: Optional[np.ndarray] = None
    suffix_row: Optional[np.ndarray] = None
    prefix_len: int = 0


class PagePool:

    def __init__(self, num_slots: int, num_pages: Optional[int],
                 page_size: int, max_decode_len: int,
                 spec_window: int = 0, overcommit: bool = False,
                 prefix_cache: bool = True):
        if max_decode_len % page_size:
            raise ValueError("max_decode_len must be a multiple "
                             "of kv_page_size")
        if num_pages is None:       # the no-deadlock capacity
            num_pages = num_slots * (max_decode_len // page_size)
        self.num_slots = num_slots
        self.num_pages = num_pages
        self.page_size = page_size
        # bytes one page id names over all pooled layers: the engine
        # sets it once its cache exists, from the leaves as they are
        # (inference.pool_page_bytes); 0 until then
        self.page_bytes = 0
        self.overcommit = overcommit
        self.prefix_cache = prefix_cache
        # spec_window widens the table so a speculative verify block
        # starting near max_decode_len spills its tail onto scratch
        # entries instead of clamping onto a real page.
        self.max_blocks = -(-(max_decode_len + spec_window)
                            // page_size)
        self._prefix_blocks = max_decode_len // page_size
        # The decode step runs the full slot batch, so INACTIVE slots
        # keep writing (masked-on-read) K/V through their rows, which
        # must never name allocatable pages: one extra physical
        # SCRATCH page absorbs those writes, as every unbacked entry.
        self.scratch_page = num_pages
        self.table = self._scratch_row(num_slots, self.max_blocks)
        self._free_pages = list(range(num_pages))
        self._avail_pages = num_pages
        self._slot_reserved = [0] * num_slots
        self._slot_pages = [[] for _ in range(num_slots)]
        self._slot_shared = [[] for _ in range(num_slots)]
        self._prefix_index: dict[bytes, int] = {}
        self._page_key: dict[int, bytes] = {}
        self._page_ref: dict[int, int] = {}
        self._lru = collections.OrderedDict()   # page -> None
        self.reset_stats()

    def seat(self, slot: int, tokens: list[int],
             remaining: int) -> Optional[Seat]:
        """Seat ``tokens`` (prompt + already generated), ``remaining``
        still to decode, in the free ``slot``: pin the longest indexed
        chain of its full pages, allocate the rest, write the table
        row. None, with no book touched, when the policy says wait."""
        page = self.page_size
        blocks = self.pages_for(len(tokens))
        worst = self.pages_for(len(tokens) + remaining)
        keys = self._page_keys(tokens) if self.prefix_cache else []
        matched = self._match_prefix(keys, len(tokens))
        m = len(matched)
        lru_m = sum(1 for pid in matched if self._page_ref[pid] == 0)
        if self.overcommit:
            # Only the prompt's pages (+1 block of headroom against
            # immediate re-thrash). Matched pages cost nothing fresh;
            # pinning an LRU-parked one consumes an evictable unit.
            want = min(blocks - m + (1 if remaining else 0), worst - m)
            if len(self._free_pages) + len(self._lru) - lru_m < want:
                return None
        else:
            # The shared prefix discounts the budget: reuse IS
            # admission headroom.
            if self._avail_pages < (worst - m) + lru_m:
                return None
            self._avail_pages -= worst - m
            self._slot_reserved[slot] = worst - m
        # Pin the matched chain: shared pages are immutable (decode
        # writes land strictly past the last full prompt page) and
        # never evictable while referenced.
        for pid in matched:
            if self._page_ref[pid] == 0:
                del self._lru[pid]
                self._avail_pages -= 1
            self._page_ref[pid] += 1
        self._slot_shared[slot] = list(matched)
        if self.prefix_cache:
            self._counts["lookups"] += 1
            self._counts["hit_pages"] += m
            self._counts["hit_tokens"] += m * page
            self._counts["total_prompt_tokens"] += len(tokens)
        fresh = [self._alloc_page() for _ in range(blocks - m)]
        self._slot_pages[slot] = fresh
        row = self._scratch_row(self.max_blocks)
        row[:m] = matched
        row[m:blocks] = fresh
        self.table[slot] = row
        seat = Seat(matched=m, row=row, keys=keys)
        if m:
            seat.prefix_len = m * page
            seat.prefix_ids = self._scratch_row(self._prefix_blocks)
            seat.prefix_ids[:m] = matched
            seat.suffix_row = self._scratch_row(self.max_blocks)
            seat.suffix_row[:blocks - m] = fresh
        return seat

    def publish(self, slot: int, seat: Seat) -> None:
        """Index the seat's fresh FULL pages, which the prefill has
        now written, for later same-prefix requests. Each moves from
        the slot's OWNED list into its SHARED set with refcount 1:
        pinned grows and the reservation shrinks by one, availability
        unchanged. The partial tail stays owned (decode writes it)."""
        for b in range(seat.matched, len(seat.keys)):
            key = seat.keys[b]
            if key in self._prefix_index:
                # Duplicate content (an exact-length twin admitted in
                # the same drain could not match its own final full
                # page): keep this copy private rather than aliasing
                # two owners onto one index entry.
                continue
            pid = int(seat.row[b])
            self._slot_pages[slot].remove(pid)
            self._slot_shared[slot].append(pid)
            self._prefix_index[key] = pid
            self._page_key[pid] = key
            self._page_ref[pid] = 1
            if self.overcommit:
                self._avail_pages -= 1
            else:
                self._slot_reserved[slot] -= 1
            self._counts["published_pages"] += 1

    def grow(self, slot: int, position: int, span: int,
             total: int) -> bool:
        """Cover the slot's next writes position..min(position + span,
        total - 1), total being its prompt + max_new_tokens: span=0
        is the one-token decode step, span=gamma the speculative
        verify block, which can cross page boundaries. Only OWNED
        pages are appended; the cap at total keeps growth inside the
        reservation (writes past it land on the scratch page). Returns
        whether the table changed. A span that runs dry half way
        (PoolDry) keeps what it appended; the next call asks for the
        rest alone."""
        needed = min(position + span, total - 1) // self.page_size + 1
        held = len(self._slot_shared[slot]) + len(
            self._slot_pages[slot])
        for block in range(held, needed):
            pid = self._alloc_page(growing=True)
            self._slot_pages[slot].append(pid)
            self.table[slot, block] = pid
        return needed > held

    def release(self, slot: int) -> None:
        """Everything the slot holds goes back: OWNED pages to the
        free list, SHARED references dropped (at refcount zero a page
        parks in the LRU, never the free list), the reservation, and
        the table row to the scratch page, which has to reach the
        device BEFORE the pages are handed out again."""
        self._release_pages(self._slot_pages[slot])
        self._slot_pages[slot] = []
        for pid in self._slot_shared[slot]:
            self._page_ref[pid] -= 1
            if self._page_ref[pid] == 0:
                self._lru[pid] = None
                self._avail_pages += 1
        self._slot_shared[slot] = []
        self._avail_pages += self._slot_reserved[slot]
        self._slot_reserved[slot] = 0
        self.table[slot] = self.scratch_page

    def clear_unreferenced(self) -> int:
        """Evict every UNREFERENCED indexed page back to the free
        list, oldest first (pinned pages stay: active slots still
        read them). Returns the number of pages reclaimed."""
        dropped = [self._evict_oldest() for _ in range(len(self._lru))]
        self._release_pages(dropped)
        return len(dropped)

    def cached_tokens(self, tokens: list[int]) -> int:
        """How many leading tokens a seat() would find in the index
        now (what admission's stall prediction discounts)."""
        return self.page_size * len(self._match_prefix(
            self._page_keys(tokens), len(tokens)))

    def pages_for(self, num_tokens: int) -> int:
        return -(-num_tokens // self.page_size)

    def stats(self) -> dict:
        """Prefix-cache counters. hit_rate is TOKEN-level: the share
        of seated prompt tokens the index turned into a gather.
        page_bytes / bytes_per_token: what a page id, and a cached
        token, hold over all pooled layers (``page_bytes``)."""
        c = self._counts
        return {
            "page_bytes": self.page_bytes,
            "bytes_per_token": self.page_bytes // self.page_size,
            "lookups": c["lookups"],
            "hit_pages": c["hit_pages"],
            "hit_tokens": c["hit_tokens"],
            "total_prompt_tokens": c["total_prompt_tokens"],
            "hit_rate": (c["hit_tokens"] / c["total_prompt_tokens"]
                         if c["total_prompt_tokens"] else 0.0),
            "indexed_pages": len(self._page_ref),
            "lru_pages": len(self._lru),
            "published_pages": c["published_pages"],
            "evictions": c["evictions"],
        }

    def reset_stats(self) -> None:
        self._counts = dict.fromkeys(
            ("lookups", "hit_pages", "hit_tokens",
             "total_prompt_tokens", "published_pages", "evictions"), 0)

    def occupancy(self, held_tokens: list[int]) -> dict:
        """The page keys of ContinuousBatcher.occupancy, a page
        counted once however many slots read it; held_tokens is what
        each seated request holds. kv_blocks_attended is the work of
        ONE layer's paged decode kernel in a step dispatched from
        this state, in (slot, page) blocks: ceil(tokens / page) for a
        seated slot and none for an idle one, which the step's mask
        hands the kernel as length 0 (slots_total - slots_active of
        the engine's occupancy is the programs a layer skips).
        ceil(live_tokens / page) is the least a kernel could do.
        kv_first_chunks_prefetched is how often that call's hand-over
        engages: every seated slot but the first finds its first
        chunk of pages fetched behind the last chunk of the seated
        slot before it (ops/paged_attention._gqa_paged_decode_kernel),
        however many idle slots lie between them."""
        return {
            "kv_pages_in_use": len(
                {page for held in self._slot_pages + self._slot_shared
                 for page in held}),
            "kv_pages_free": len(self._free_pages),
            "kv_pages_lru": len(self._lru),
            "kv_pages_total": self.num_pages,
            "prefix_index_pages": len(self._page_ref),
            "kv_blocks_attended": sum(
                self.pages_for(tokens) for tokens in held_tokens),
            "kv_first_chunks_prefetched": max(len(held_tokens) - 1, 0),
        }

    def check(self) -> None:
        """AssertionError unless FREE / LRU / OWNED / PINNED partition
        the pool, refcounts equal the slots' references, each table row
        holds its slot's pages and availability balances. For tests."""
        free, lru = list(self._free_pages), list(self._lru)
        owned = [p for pages in self._slot_pages for p in pages]
        pinned = [p for p, ref in self._page_ref.items() if ref > 0]
        assert set(lru) == {p for p, ref in self._page_ref.items()
                            if ref == 0}, "LRU is not the refcount-0 set"
        everything = free + lru + owned + pinned
        assert len(everything) == len(set(everything)), \
            "a page appears in two lifecycle states at once"
        assert sorted(everything) == list(range(self.num_pages)), \
            "pages leaked or double-counted"
        live_refs = collections.Counter(
            p for shared in self._slot_shared for p in shared)
        assert live_refs == {p: self._page_ref[p] for p in pinned}, \
            "refcounts out of sync with slot references"
        for slot in range(self.num_slots):
            held = self._slot_shared[slot] + self._slot_pages[slot]
            row = self.table[slot]
            assert sorted(row[row != self.scratch_page]) == \
                sorted(held), f"slot {slot}'s table row is not its pages"
        assert self._avail_pages == (
            self.num_pages - len(pinned) - sum(self._slot_reserved)), \
            "_avail_pages != total - pinned - reserved"

    def _scratch_row(self, *shape: int) -> np.ndarray:
        return np.full(shape, self.scratch_page, np.int32)

    def _alloc_page(self, growing: bool = False) -> int:
        """THE single page-allocation path: the free list's end,
        then the oldest unreferenced indexed page (never a pinned
        one). _release_pages is the only way back (the
        serving-page-refcount lint rule pins both)."""
        if self._free_pages:
            return self._free_pages.pop()
        if self._lru:
            self._counts["evictions"] += 1
            return self._evict_oldest()
        if self.overcommit and growing:
            raise PoolDry
        raise RuntimeError(
            "paged KV pool exhausted mid-decode; size "
            "kv_num_pages >= num_slots * max_decode_len / "
            "page_size to rule this out, or enable "
            "overcommit=True for preemption")

    def _release_pages(self, pages: list[int]) -> None:
        """THE single way back to the free list: release() and
        clear_unreferenced() call it once no row or index entry
        names the pages."""
        self._free_pages.extend(pages)

    def _evict_oldest(self) -> int:
        pid, _ = self._lru.popitem(last=False)
        key = self._page_key.pop(pid)
        if self._prefix_index.get(key) == pid:
            del self._prefix_index[key]
        del self._page_ref[pid]
        return pid

    def _page_keys(self, tokens: list[int]) -> list[bytes]:
        """Chained content hash per FULL page: key_b covers tokens
        [0, (b+1)*page) via H(key_{b-1} || tokens of page b), so a
        key names the whole prefix up to its page boundary: matching
        compares no token ids, and equal pages under different
        prefixes never collide."""
        keys: list[bytes] = []
        prev = b""
        page = self.page_size
        for b in range(len(tokens) // page):
            prev = hashlib.blake2b(
                prev + np.asarray(tokens[b * page:(b + 1) * page],
                                  np.int64).tobytes(),
                digest_size=16).digest()
            keys.append(prev)
        return keys

    def _match_prefix(self, keys: list[bytes],
                      num_tokens: int) -> list[int]:
        """Longest indexed page chain, capped to leave one suffix token
        (the first sample needs real last-token logits)."""
        limit = (num_tokens - 1) // self.page_size
        matched: list[int] = []
        for key in keys[:limit]:
            pid = self._prefix_index.get(key)
            if pid is None:
                break
            matched.append(pid)
        return matched

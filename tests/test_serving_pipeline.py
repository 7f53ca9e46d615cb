"""One decode step stays in flight (ContinuousBatcher._step): step k+1
is dispatched from the host's books before step k's tokens are read
back, an admission's prefill is dispatched BEHIND the step in flight
with its first token seated on the device, and everything that edits
slots outside that order settles what is unread first. CPU, float32,
a tiny model: what every request is served, in which order its tokens
arrive, what an eos finish, a cancel, a drain and a preemption see
with a step in flight or a first token pending, that nothing before
the readback reads from the device, and the counters the mechanism
brings."""

import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import array as jax_array

from batch_shipyard_tpu.models import inference as inf
from batch_shipyard_tpu.models import serving
from batch_shipyard_tpu.models import transformer as tfm

CFG = tfm.TransformerConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=2, d_head=16,
    d_ff=64, max_seq_len=64, dtype=jnp.float32,
    param_dtype=jnp.float32)
PAGE = 8
# kind -> (kv_cache_dtype, ContinuousBatcher keywords)
KINDS = {
    "paged": (None, {"kv_page_size": PAGE}),
    "dense": (None, {}),
    "paged-int8": ("int8", {"kv_page_size": PAGE}),
}


@pytest.fixture(scope="module")
def params():
    return tfm.TransformerLM(CFG).init(
        jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32))["params"]


def _config(kind):
    return dataclasses.replace(CFG, kv_cache_dtype=KINDS[kind][0])


class Tokens:
    """An observer of the engine's tokens: (token, index) per request
    in arrival order, whichever hook brought them: on_token (called
    a token) or on_tokens (called once with all a landed step or a
    prefill produced; ``batches`` keeps each call's size)."""

    def __init__(self):
        self.seen: dict = {}
        self.batches: list = []

    def __call__(self, request_id, token, index):
        self.seen.setdefault(request_id, []).append((token, index))

    def batch(self, triples):
        self.batches.append(len(triples))
        for triple in triples:
            self(*triple)

    def tokens(self, request_id):
        return [token for token, _ in self.seen.get(request_id, [])]

    def in_order_from(self, request_id, first=0):
        """Indices first, first+1, ...: no gap and no repeat."""
        indices = [index for _, index in self.seen[request_id]]
        return indices == list(range(first, first + len(indices)))


HOOKS = ("on_token", "on_tokens")


@pytest.fixture(params=HOOKS)
def hook(request):
    """Which of the engine's two token hooks the observer hangs on:
    on_tokens where it is set, on_token (a call a token) as the
    fallback for whoever sets only that."""
    return request.param


def _engine(kind, params, num_slots=3, hook="on_token", **kwargs):
    observer = Tokens()
    engine = serving.ContinuousBatcher(
        _config(kind), params, num_slots=num_slots, max_decode_len=64,
        on_token=observer if hook == "on_token" else None,
        **KINDS[kind][1], **kwargs)
    if hook == "on_tokens":
        engine.on_tokens = observer.batch
    return engine, observer


_decoders: dict = {}


def _reference(kind, params, prompt, new_tokens):
    """The unbatched greedy decoder (models/inference has a loop and
    a cache of its own) on one prompt."""
    if kind not in _decoders:
        _decoders[kind] = inf.make_decoder(_config(kind), params,
                                           max_decode_len=64)[0]
    out, _cache = _decoders[kind](
        jnp.asarray([prompt], jnp.int32), new_tokens,
        jax.random.PRNGKey(0))
    return [int(t) for t in np.asarray(out[0, len(prompt):])]


def _requests(seed, sizes, name="r"):
    rng = np.random.RandomState(seed)
    return [serving.Request(
        f"{name}{i}", [int(t) for t in rng.randint(1, 97, (prompt,))],
        max_new_tokens=new) for i, (prompt, new) in enumerate(sizes)]


def _step(engine, done):
    """One step; afterwards the books balance, an idle engine has
    nothing in flight, and between calls the host is at most one
    token behind the device."""
    for request_id, tokens in engine.step():
        assert request_id not in done
        done[request_id] = tokens
    if engine.pages is not None:
        engine.pages.check()
    assert all(slot.in_flight in (0, 1) for slot in engine._slots)
    if not any(slot.request for slot in engine._slots):
        assert engine._in_flight is None
    return done


def _drain(engine, done=None):
    done = {} if done is None else done
    for _ in range(500):
        if not engine.pending():
            return done
        _step(engine, done)
    raise AssertionError("engine failed to drain")


# ------------- (a) what every request is served, and in what order -------

SIZES = [(5, 9), (17, 1), (3, 14), (9, 2), (20, 7), (6, 12), (11, 3),
         (4, 15), (13, 5), (8, 8)]


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_fixed_schedule_serves_the_unbatched_greedy_tokens(
        kind, params, hook):
    engine, observer = _engine(kind, params, hook=hook)
    requests = _requests(2, SIZES)
    waiting = list(requests)
    done: dict = {}
    for call in range(500):
        if call % 3 == 0 and waiting:
            for req in waiting[:2]:
                engine.submit(req)
            del waiting[:2]
        if not waiting and not engine.pending():
            break
        _step(engine, done)
    assert sorted(done) == sorted(r.request_id for r in requests)
    for req in requests:
        want = _reference(kind, params, req.prompt, req.max_new_tokens)
        assert done[req.request_id] == want, req.request_id
        assert observer.tokens(req.request_id) == want
        assert observer.in_order_from(req.request_id)
    stats = engine.step_stats()
    assert stats["overshoot_tokens"] == 0
    assert 0 < stats["steps_overlapped"] < stats["decode_steps"]
    assert engine.occupancy()["slots_active"] == 0
    if hook == "on_tokens":
        # ONE hand-over a landed step and one a prefill's first token
        assert len(observer.batches) == (stats["decode_steps"]
                                         + len(requests))
        assert max(observer.batches) == 3 and min(observer.batches) == 1


# ------------------------------ (b) an eos finish ------------------------

def _eos_case(kind, params, seed):
    """A request whose greedy stream holds a token that first shows
    up mid-stream: with it as eos_id the request ends there."""
    for prompt_len in range(4, 30):
        req = _requests(seed, [(prompt_len, 12)], name="eos")[0]
        stream = _reference(kind, params, req.prompt, 12)
        for j in range(2, 9):
            if stream[j] not in stream[:j]:
                req.eos_id = stream[j]
                return req, stream[:j + 1]
    raise AssertionError("no stream with a fresh token mid-way")


@pytest.mark.parametrize("kind", ["paged", "dense"])
def test_an_eos_finish_discards_the_overshoot_and_frees_the_slot(
        kind, params, hook):
    """The step in flight when the eos is read has decoded the
    request once more: that token reaches nobody, the counter has it,
    and the next tenant of the slot is served as if alone."""
    engine, observer = _engine(kind, params, num_slots=2, hook=hook)
    ender, want = _eos_case(kind, params, seed=4)
    beside, tenant = _requests(6, [(7, 20), (10, 6)], name="n")
    engine.submit(ender)
    engine.submit(beside)
    engine.submit(tenant)           # waits for the eos'd slot
    done = _drain(engine)
    assert done["eos0"] == want == observer.tokens("eos0")
    assert observer.in_order_from("eos0")
    assert engine.step_stats()["overshoot_tokens"] == 1
    for req in (beside, tenant):
        assert done[req.request_id] == _reference(
            kind, params, req.prompt, req.max_new_tokens)
        assert observer.in_order_from(req.request_id)


def test_an_eos_that_ends_the_last_request_leaves_nothing_in_flight(
        params, hook):
    engine, observer = _engine("paged", params, num_slots=2, hook=hook)
    ender, want = _eos_case("paged", params, seed=8)
    engine.submit(ender)
    done = _drain(engine)
    assert done == {"eos0": want}
    stats = engine.step_stats()
    # the overshoot step was dispatched, then settled as the engine
    # fell idle: pending() is false with nothing unread
    assert stats["overshoot_tokens"] == 1
    assert stats["settles"]["idle"] == 1
    assert stats["decode_steps"] == len(want)
    assert engine._in_flight is None and not engine.pending()
    assert engine.occupancy()["kv_pages_in_use"] == 0
    # the overshoot step lands no token: no hand-over for it
    assert observer.tokens("eos0") == want
    assert observer.batches == ([1] * len(want)
                                if hook == "on_tokens" else [])


# --------- (c) cancel, drain and preemption with a step in flight --------

def test_a_cancel_settles_first_and_a_resume_loses_no_token(
        params, hook):
    engine, observer = _engine("paged", params, num_slots=2, hook=hook)
    victim, beside = _requests(3, [(6, 14), (9, 5)], name="c")
    engine.submit(victim)
    engine.submit(beside)
    done: dict = {}
    for _ in range(4):
        _step(engine, done)
    # beside's fifth and last token is in flight; victim's fifth too
    assert engine._in_flight is not None and not done
    assert [s.in_flight for s in engine._slots] == [1, 1]
    assert engine.cancel("c0")
    assert engine.step_stats()["settles"]["cancel"] == 1
    served = observer.tokens("c0")
    want = _reference("paged", params, victim.prompt, 14)
    assert served == want[:5] and observer.in_order_from("c0")
    # the settle finished the neighbour between two steps: it is
    # owed to the caller, so the engine is not idle yet
    assert engine.active_request_ids() == [] and engine.pending() == 1
    assert not engine.cancel("c1")      # finished, not cancellable
    _step(engine, done)
    assert done == {"c1": _reference("paged", params, beside.prompt, 5)}
    assert not engine.pending()
    # the router's resume: the served tokens go back in, nothing is
    # decoded twice and the stream goes on where it stopped
    engine.submit(victim, resumed=served)
    _drain(engine, done)
    assert done["c0"] == want
    assert observer.tokens("c0") == want     # 5, then 9 more
    assert [i for _, i in observer.seen["c0"]] == list(range(14))


def test_a_drain_settles_first_and_the_active_requests_finish(
        params, hook):
    engine, observer = _engine("dense", params, num_slots=2, hook=hook)
    requests = _requests(5, [(5, 8), (8, 6), (4, 4), (7, 3)], name="d")
    for req in requests:
        engine.submit(req)
    done: dict = {}
    for _ in range(3):
        _step(engine, done)
    assert engine._in_flight is not None
    before = {rid: len(observer.tokens(rid)) for rid in ("d0", "d1")}
    assert engine.drain() == ["d2", "d3"]
    assert engine.step_stats()["settles"]["drain"] == 1
    assert engine._in_flight is None
    assert {rid: len(observer.tokens(rid)) for rid in before} == {
        rid: n + 1 for rid, n in before.items()}
    with pytest.raises(ValueError, match="draining"):
        engine.submit(requests[2])
    _drain(engine, done)
    assert sorted(done) == ["d0", "d1"]
    for req in requests[:2]:
        assert done[req.request_id] == _reference(
            "dense", params, req.prompt, req.max_new_tokens)
        assert observer.in_order_from(req.request_id)
    assert engine.drain() == []         # idempotent


def _preempted_run(params, hook):
    engine, observer = _engine("paged", params, num_slots=3,
                               kv_num_pages=7, overcommit=True,
                               hook=hook)
    requests = _requests(9, [(14, 18), (9, 20), (12, 16), (6, 10),
                             (15, 12)], name="p")
    for req in requests:
        engine.submit(req)
    return engine, observer, requests, _drain(engine)


def test_a_preemption_with_a_step_in_flight_loses_and_doubles_nothing(
        params, hook):
    """Overcommit on a pool too small for its slots: a dry pool lands
    the step in flight before it evicts anybody, so the victim goes
    back to the queue with every token it was served, and resumes
    after them."""
    engine, observer, requests, done = _preempted_run(params, hook)
    stats = engine.step_stats()
    assert engine.preemptions >= 2
    assert stats["settles"]["preempt"] >= engine.preemptions
    assert stats["overshoot_tokens"] == 0
    for req in requests:
        want = _reference("paged", params, req.prompt,
                          req.max_new_tokens)
        assert done[req.request_id] == want, req.request_id
        # the observer saw each index once, in order, across the
        # re-queue
        assert observer.tokens(req.request_id) == want
        assert observer.in_order_from(req.request_id)


def test_both_hooks_see_the_same_sequence_across_a_preemption(params):
    """The same schedule once through on_token and once through
    on_tokens: the same (token, index) sequence a request, the
    re-queued ones among them."""
    runs = {hook: _preempted_run(params, hook) for hook in HOOKS}
    (one, by_one, _, _), (batched, by_batch, _, _) = (
        runs["on_token"], runs["on_tokens"])
    assert one.preemptions == batched.preemptions >= 2
    assert by_one.seen == by_batch.seen and len(by_one.seen) == 5
    assert by_one.batches == [] and sum(by_batch.batches) == sum(
        len(seen) for seen in by_batch.seen.values())


# -------- (d) nothing before the readback reads from the device ---------

def test_no_phase_before_the_readback_reads_from_the_device(
        params, monkeypatch):
    """Decode-only calls, pages growing under them: every read of a
    device array happens inside _land (the readback of the step
    before), none while the pages grow and the step is dispatched.
    The CPU backend does not enforce jax.transfer_guard, so the reads
    themselves are watched: ArrayImpl._value (int(), bool(), tolist(),
    __array__) and numpy.asarray of a jax array."""
    engine, _observer = _engine("paged", params)
    for req in _requests(1, [(6, 20), (7, 24), (13, 22)]):
        engine.submit(req)
    done: dict = {}
    _step(engine, done)             # the prefills read their first token
    reads: list = []
    landing = [False]
    value = jax_array.ArrayImpl._value
    asarray, land = np.asarray, engine._land

    def watched_value(self):
        reads.append(("value", landing[0]))
        return value.fget(self)

    def watched_asarray(a, *args, **kwargs):
        if isinstance(a, jax.Array):
            reads.append(("asarray", landing[0]))
        return asarray(a, *args, **kwargs)

    def watched_land(step):
        landing[0] = True
        try:
            land(step)
        finally:
            landing[0] = False

    monkeypatch.setattr(jax_array.ArrayImpl, "_value",
                        property(watched_value))
    monkeypatch.setattr(np, "asarray", watched_asarray)
    monkeypatch.setattr(engine, "_land", watched_land)
    grown = engine.occupancy()["kv_pages_in_use"]
    for _ in range(15):
        engine.step()
    assert engine.occupancy()["kv_pages_in_use"] > grown
    assert len(reads) >= 15
    assert all(inside for _, inside in reads), reads
    # the watch itself works: a read outside _land is seen as one
    int(engine._tokens[0, 0])
    assert reads[-1] == ("value", False)


# ------------------------------ (e) the counters -------------------------

def test_every_decode_step_overlaps_but_the_first_and_the_settled(
        params, recorder):
    """Two slots. a (10 tokens) is seated alone in call 1 and decodes
    in steps 1-9. b (3 tokens) and c (5) arrive before call 3: b's
    prefill is dispatched there BEHIND step 2 (no settle), b decodes
    in steps 3-4 and ends in call 5; c takes its slot in call 6, its
    prefill behind step 5, and decodes in steps 6-9. Call 10 has
    nothing to dispatch and reads step 9 back (idle). Only step 1
    has no predecessor in flight, only a's prefill nothing before
    it."""
    engine, observer = _engine("paged", params, num_slots=2)
    a, b, c = _requests(7, [(4, 10), (6, 3), (5, 5)], name="e")
    engine.submit(a)
    done: dict = {}
    _step(engine, done)
    _step(engine, done)
    engine.submit(b)
    engine.submit(c)
    calls = 2
    while engine.pending():
        _step(engine, done)
        calls += 1
    assert calls == 10 and sorted(done) == ["e0", "e1", "e2"]
    for req in (a, b, c):
        assert done[req.request_id] == _reference(
            "paged", params, req.prompt, req.max_new_tokens)
        assert observer.in_order_from(req.request_id)
    stats = engine.step_stats()
    assert stats["steps"] == 10 and stats["decode_steps"] == 9
    assert "admit" not in serving.SETTLE_CAUSES
    assert stats["settles"] == {"preempt": 0, "cancel": 0, "drain": 0,
                                "idle": 1}
    assert stats["steps_overlapped"] == 9 - 1
    assert (stats["prefills"], stats["prefills_overlapped"]) == (3, 2)
    assert stats["overshoot_tokens"] == 0
    # the rows say the same, call by call
    rows = [row["attrs"] for row in recorder()]
    assert [row["overlapped"] for row in rows] == [
        0, 1, 1, 1, 1, 1, 1, 1, 1, 0]
    assert [row["prefills"] for row in rows] == [
        1, 0, 1, 0, 0, 1, 0, 0, 0, 0]
    assert [row["prefills_overlapped"] for row in rows] == [
        0, 0, 1, 0, 0, 1, 0, 0, 0, 0]
    assert [row["settles"] for row in rows] == [[]] * 9 + [["idle"]]
    assert [row["overshoot_tokens"] for row in rows] == [0] * 10
    assert [row["slots_active"] for row in rows] == [
        0, 1, 1, 2, 1, 1, 2, 2, 2, 0]
    # a call emits the step BEFORE the one it dispatches and, behind
    # it, its own prefills' first tokens: call 2 has step 1's token,
    # call 3 step 2's and b's first, the last call the last step's two
    assert [sum(x.get("rows", 1) for x in row["landed"])
            for row in rows] == [1, 1, 2, 2, 2, 2, 2, 2, 2, 2]
    assert [[x["kind"] for x in row["landed"]] for row in rows][:3] == \
        [["prefill"], ["decode"], ["decode", "prefill"]]
    assert [row["finished"] for row in rows] == [
        0, 0, 0, 0, 1, 0, 0, 0, 0, 2]
    assert rows[-1]["dispatch_ms"] == 0 < rows[-1]["readback_ms"]
    # an admitting call waits for its first token inside "prefill",
    # after its decode step was dispatched
    assert all((row["prefill_ms"] > 0) == (row["prefills"] > 0)
               for row in rows)


# ------------------------- (f) the sampled stream ------------------------

# Recorded on the commit before the lookahead (PR 29's, 72efe5f) with
# this very engine: the key is split once for the prefill's sample and
# once per dispatched decode step, in dispatch order.
SAMPLED = [42, 81, 25, 48, 94, 52, 30, 94, 30, 43, 96, 48, 81, 48, 81,
           60]


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_sampled_stream_is_the_serial_engines_for_the_same_key(
        kind, params):
    engine, _observer = _engine(
        kind, params, seed=5,
        sampling=inf.SamplingConfig(temperature=0.9, top_k=20))
    engine.submit(serving.Request(
        "s", [3, 14, 15, 92, 65, 35, 89, 79, 32], max_new_tokens=16))
    assert _drain(engine) == {"s": SAMPLED}


# ------------------- (g) admission without a settle ----------------------

SAMPLING = {"greedy": inf.SamplingConfig(),
            "sampled": inf.SamplingConfig(temperature=0.9, top_k=20)}


def _admissions_behind_a_step(engine, late):
    """One request decoding, then ``late`` more submitted before ONE
    call: their prefills go behind the step in flight, one behind the
    other. -> what every request was served."""
    first, *others = _requests(12, [(7, 12), (5, 6), (19, 9), (11, 4)],
                               name="g")
    engine.submit(first)
    done: dict = {}
    _step(engine, done)
    _step(engine, done)
    for req in others[:late]:
        engine.submit(req)
    _step(engine, done)
    return [first] + others[:late], _drain(engine, done)


@pytest.mark.parametrize("late", [2, 3])
@pytest.mark.parametrize("sampling", sorted(SAMPLING))
@pytest.mark.parametrize("kind", list(KINDS))
def test_admissions_behind_a_step_serve_the_serial_orders_tokens(
        kind, sampling, late, params, serialised):
    engine, observer = _engine(kind, params, num_slots=4, seed=3,
                               sampling=SAMPLING[sampling])
    requests, done = _admissions_behind_a_step(engine, late)
    stats = engine.step_stats()
    assert stats["prefills"] == 1 + late
    assert stats["prefills_overlapped"] == late
    assert stats["steps_overlapped"] == stats["decode_steps"] - 1
    assert stats["settles"]["idle"] == 1 == sum(
        stats["settles"].values())
    serial, _ = _engine(kind, params, num_slots=4, seed=3,
                        sampling=SAMPLING[sampling])
    _requests_, want = _admissions_behind_a_step(serialised(serial),
                                                 late)
    assert serial.step_stats()["steps_overlapped"] == 0
    assert serial.step_stats()["prefills_overlapped"] == late - 1
    assert done == want and len(done) == 1 + late
    for req in requests:
        assert observer.tokens(req.request_id) == done[req.request_id]
        assert observer.in_order_from(req.request_id)
        if sampling == "greedy":
            assert done[req.request_id] == _reference(
                kind, params, req.prompt, req.max_new_tokens)


def _one_decoding(params, hook="on_token", **kwargs):
    """An engine with one request two steps into its decode, a step in
    flight, and a second request queued."""
    engine, observer = _engine("paged", params, num_slots=2, hook=hook,
                               **kwargs)
    beside, late = _requests(3, [(6, 14), (9, 8)], name="c")
    engine.submit(beside)
    done: dict = {}
    _step(engine, done)
    _step(engine, done)
    engine.submit(late)
    return engine, observer, beside, late, done


def _unread_kinds(engine):
    return [type(result).__name__ for result in engine._unread]


@pytest.mark.parametrize("when", ["before", "after"])
def test_a_cancel_with_a_first_token_pending_delivers_it_first(
        when, params):
    """The cancelled request's prefill has been dispatched and its
    first token is unread, either before the call's decode step is
    dispatched (what _admit leaves) or after it (from the observer,
    as the call hands over the step before): the cancel lands what is
    unread in the device's order, so the first token (and the decode
    step's, where one was dispatched behind it) reaches the observer
    once, and a resume goes on after them."""
    engine, observer, beside, late, done = _one_decoding(
        params, hook="on_tokens")
    seen: list = []
    if when == "before":
        engine._admit()         # what step() does first
        seen.append(_unread_kinds(engine))
        assert engine.cancel("c1")
    else:
        def cancelling(triples):
            observer.batch(triples)
            if not seen:
                seen.append(_unread_kinds(engine))
                assert engine.cancel("c1")

        engine.on_tokens = cancelling
        _step(engine, done)
    assert seen == [["_InFlight", "_FirstToken"] if when == "before"
                    else ["_FirstToken", "_InFlight"]]
    assert not engine._unread
    assert engine.step_stats()["settles"]["cancel"] == 1
    want = _reference("paged", params, late.prompt, 8)
    served = observer.tokens("c1")
    assert served == want[:1 if when == "before" else 2]
    assert observer.in_order_from("c1")
    assert engine.active_request_ids() == ["c0"]
    engine.submit(late, resumed=served)
    _drain(engine, done)
    assert done["c1"] == want == observer.tokens("c1")
    assert [i for _, i in observer.seen["c1"]] == list(range(8))
    assert done["c0"] == _reference("paged", params, beside.prompt, 14)
    assert observer.in_order_from("c0")
    assert engine.step_stats()["overshoot_tokens"] == 0


def test_a_drain_with_a_first_token_pending_delivers_it_first(
        params, hook):
    engine, observer, beside, late, done = _one_decoding(params, hook)
    queued = _requests(5, [(4, 4)], name="q")[0]
    engine.submit(queued)
    engine._admit()             # what step() does first
    assert _unread_kinds(engine) == ["_InFlight", "_FirstToken"]
    assert engine.drain() == ["q0"]
    assert not engine._unread
    assert engine.step_stats()["settles"]["drain"] == 1
    want = _reference("paged", params, late.prompt, 8)
    assert observer.tokens("c1") == want[:1]
    _drain(engine, done)
    assert done == {"c0": _reference("paged", params, beside.prompt,
                                     14), "c1": want}
    for rid in done:
        assert observer.tokens(rid) == done[rid]
        assert observer.in_order_from(rid)


def test_a_dry_pool_lands_a_pending_first_token_before_it_evicts(
        params, hook):
    """Overcommit, three pages. a (one page) needs its second page in
    call 3, the call that seats b on the other two: the pool is dry
    with b's first token unread behind the step in flight. Both are
    landed before anybody is evicted, so b, the victim, goes back to
    the queue with the token it was served and resumes after it."""
    engine, observer = _engine("paged", params, num_slots=2,
                               kv_num_pages=3, overcommit=True,
                               hook=hook)
    settle, settled = engine._settle, []

    def watched_settle(cause):
        settled.append((cause, _unread_kinds(engine)))
        return settle(cause)

    engine._settle = watched_settle
    a, b = _requests(9, [(6, 10), (9, 6)], name="p")
    engine.submit(a)
    done: dict = {}
    _step(engine, done)
    _step(engine, done)
    engine.submit(b)
    _step(engine, done)
    assert settled == [("preempt", ["_InFlight", "_FirstToken"]),
                       ("preempt", [])]
    assert engine.preemptions == 1
    assert engine.active_request_ids() == ["p0"]
    want = _reference("paged", params, b.prompt, 6)
    assert observer.tokens("p1") == want[:1]
    assert [entry.resumed for entry in engine._queue] == [want[:1]]
    _drain(engine, done)
    assert engine.preemptions == 1
    assert engine.step_stats()["overshoot_tokens"] == 0
    assert done == {"p0": _reference("paged", params, a.prompt, 10),
                    "p1": want}
    for rid in done:
        assert observer.tokens(rid) == done[rid]
        assert observer.in_order_from(rid)


@pytest.mark.parametrize("kind", ["paged", "dense"])
def test_a_request_of_one_token_joins_no_decode_step(kind, params,
                                                     hook):
    """max_new_tokens 1: the books know before the dispatch that the
    first token is the last, so the decode step behind the prefill
    does not seat the slot, nothing overshoots, and the request comes
    back from the call that admitted it."""
    engine, observer = _engine(kind, params, num_slots=2, hook=hook)
    beside, single, tenant = _requests(
        13, [(7, 9), (10, 1), (5, 4)], name="o")
    engine.submit(beside)
    done: dict = {}
    _step(engine, done)
    _step(engine, done)
    engine.submit(single)
    engine.submit(tenant)       # waits for the single's slot
    steps = engine.step_stats()["decode_steps"]
    _step(engine, done)
    assert list(done) == ["o1"]
    assert engine.step_stats()["decode_steps"] == steps + 1
    assert [req.request_id for _, req in engine._in_flight.seated] \
        == ["o0"]
    _drain(engine, done)
    assert engine.step_stats()["overshoot_tokens"] == 0
    for req in (beside, single, tenant):
        assert done[req.request_id] == _reference(
            kind, params, req.prompt, req.max_new_tokens)
        assert observer.tokens(req.request_id) == done[req.request_id]
        assert observer.in_order_from(req.request_id)


@pytest.mark.parametrize("alone", [False, True])
@pytest.mark.parametrize("kind", ["paged", "dense"])
def test_an_eos_on_the_first_token_costs_one_overshoot_step(
        kind, alone, params, hook):
    """The host learns of an eos from the token, and the decode step
    behind the prefill was dispatched before the first token was
    read: it decodes the ended request once more, that token reaches
    nobody and is counted, and the slot's next tenant is served as if
    alone."""
    engine, observer = _engine(kind, params, num_slots=2, hook=hook)
    beside, ender, tenant = _requests(
        17, [(7, 9), (10, 6), (5, 4)], name="f")
    ender.eos_id = _reference(kind, params, ender.prompt, 1)[0]
    done: dict = {}
    if not alone:
        engine.submit(beside)
        _step(engine, done)
        _step(engine, done)
    engine.submit(ender)
    engine.submit(tenant)
    _drain(engine, done)
    stats = engine.step_stats()
    assert stats["overshoot_tokens"] == 1
    assert done["f1"] == [ender.eos_id] == observer.tokens("f1")
    for req in (tenant,) if alone else (beside, tenant):
        assert done[req.request_id] == _reference(
            kind, params, req.prompt, req.max_new_tokens)
        assert observer.in_order_from(req.request_id)
    if engine.pages is not None:
        assert engine.occupancy()["kv_pages_in_use"] == 0


def test_a_prefill_time_sample_is_the_devices_time_for_it(params):
    """The prefill's launch record (the sample _should_defer learns
    from) runs from the later of its dispatch and the landing before
    it to its token: the sample of a prefill dispatched behind a step
    in flight does not hold that step's wait."""
    engine, _observer, _beside, late, done = _one_decoding(params)
    _step(engine, done)
    step, prefill = list(engine._ring)[-2:]
    assert (step.kind, prefill.kind) == ("decode", "prefill")
    assert (prefill.request_id, prefill.path, prefill.bucket) == \
        (late.request_id, "cold", 16)
    # the step in flight landed first, after the prefill's dispatch
    assert prefill.dispatched_at < step.landed_at < prefill.landed_at
    assert prefill.period_ms == pytest.approx(
        (prefill.landed_at - step.landed_at) * 1e3)
    assert prefill.behind_ms > 0 and prefill.queued == 1


# ------------- (h) nothing compiles for the first real admission ---------

def test_a_warmed_engine_compiles_nothing_for_its_first_admissions(
        params):
    engine, _observer = _engine("paged", params, num_slots=3)
    assert engine.warmup() == [16, 32, 64]
    before = engine.step_stats()["compiles"]
    requests, done = _admissions_behind_a_step(engine, 3)
    assert len(done) == 4
    assert engine.step_stats()["compiles"] == before


_PRECOMPILED = r"""
import json, os, sys
sys.path.insert(0, os.environ["REPO_ROOT"])
import jax
import jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from batch_shipyard_tpu.models import serving
from batch_shipyard_tpu.models import transformer as tfm
config = tfm.TransformerConfig(
    vocab_size=97, d_model=32, n_layers=1, n_heads=2, d_head=16,
    d_ff=64, max_seq_len=32, dtype=jnp.float32,
    param_dtype=jnp.float32)
params = tfm.TransformerLM(config).init(
    jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32))["params"]
cache_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
out = {}
for name, kwargs in (
        ("paged", {"kv_page_size": 8}), ("dense", {}),
        ("pinned", {"kv_page_size": 8, "device": jax.devices()[0]})):
    engine = serving.ContinuousBatcher(
        config, params, num_slots=2, max_decode_len=32, **kwargs)
    count = engine.precompile()
    built = set(os.listdir(cache_dir))
    for i, length in enumerate((5, 20)):
        engine.submit(serving.Request(
            f"r{i}", list(range(1, length + 1)), max_new_tokens=3))
    while engine.pending():
        engine.step()
    out[name] = {"count": count, "buckets": engine.warmup_buckets(),
                 "built": sorted(built),
                 "later": sorted(set(os.listdir(cache_dir)) - built)}
print(json.dumps(out))
"""


def test_a_precompiled_engine_compiles_no_step_program_later(tmp_path):
    """precompile() lowers the decode step, every prefill bucket and
    the seat program as the real calls trace them: with the
    persistent cache on, the first real admissions and decode steps
    add no entry for any of them (eager bookkeeping ops may)."""
    env = {k: v for k, v in os.environ.items()
           if k != "SHIPYARD_COMPILE_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               REPO_ROOT=str(pathlib.Path(__file__).resolve().parents[1]),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, "-c", _PRECOMPILED], capture_output=True,
        text=True, timeout=600, env=env, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    programs = ("jit__decode_step", "jit__prefill", "jit__seat_first")
    for name, run in out.items():
        # the decode step, a prefill a bucket, the seat program
        assert run["count"] == 1 + len(run["buckets"]) + 1, name
        assert any(entry.startswith("jit__seat_first")
                   for entry in run["built"]), (name, run["built"])
        assert not [entry for entry in run["later"]
                    if entry.startswith(programs)], (name, run["later"])

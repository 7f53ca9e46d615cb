"""One decode step stays in flight (ContinuousBatcher._step): step k+1
is dispatched from the host's books before step k's tokens are read
back, and everything that edits slots outside that order settles the
step in flight first. CPU, float32, a tiny model: what every request
is served, in which order its tokens arrive, what an eos finish, a
cancel, a drain and a preemption see with a step in flight, that
nothing before the readback reads from the device, and the counters
the mechanism brings."""

import dataclasses

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
    in steps 1-9. b (3 tokens) and c (5) arrive before call 3: b is
    seated there (a settle), decodes in steps 3-4 and ends in call 5;
    c takes its slot in call 6 (a settle) and decodes in steps 6-9.
    Call 10 has nothing to dispatch and reads step 9 back (idle)."""
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
    stats = engine.step_stats()
    assert stats["steps"] == 10 and stats["decode_steps"] == 9
    assert stats["settles"] == {"admit": 2, "preempt": 0, "cancel": 0,
                                "drain": 0, "idle": 1}
    assert stats["steps_overlapped"] == 9 - 1 - 2
    assert stats["overshoot_tokens"] == 0
    # the rows say the same, call by call
    rows = [row["attrs"] for row in recorder()]
    assert [row["overlapped"] for row in rows] == [
        0, 1, 0, 1, 1, 0, 1, 1, 1, 0]
    assert [row["settles"] for row in rows] == [
        [], [], ["admit"], [], [], ["admit"], [], [], [], ["idle"]]
    assert [row["overshoot_tokens"] for row in rows] == [0] * 10
    assert [row["slots_active"] for row in rows] == [
        0, 1, 1, 2, 1, 1, 2, 2, 2, 0]
    # a call emits the step BEFORE the one it dispatches: call 2 has
    # step 1's token, the last call the last step's two
    assert [row["tokens_emitted"] for row in rows] == [
        1, 1, 2, 2, 2, 2, 2, 2, 2, 2]
    assert [row["finished"] for row in rows] == [
        0, 0, 0, 0, 1, 0, 0, 0, 0, 2]
    assert rows[-1]["dispatch_ms"] == 0 < rows[-1]["readback_ms"]


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

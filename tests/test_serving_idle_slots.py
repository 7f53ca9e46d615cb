"""A slot that holds no request costs the decode step nothing: every
step program (models/serving._decode_step, _speculative_step) leaves
an idle slot's per-layer cache cursor at 0, so the decode kernels,
which skip key blocks by the cursor, stop attending over the scratch
page (or a dense row nobody reads) at a freed slot's old length, and
hands its ``active`` mask to the model, which hands a paged pool's
attention call length 0 for every slot the mask leaves out: zeros on
every road, no page fetched and no tile computed on the kernels'. A
live slot's cursor, keys and tokens are what they were."""

import contextlib
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

from batch_shipyard_tpu.models import inference as inf
from batch_shipyard_tpu.models import serving
from batch_shipyard_tpu.models import transformer as tfm

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent
CFG = tfm.TransformerConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=2, d_head=16,
    d_ff=64, max_seq_len=96, dtype=jnp.float32,
    param_dtype=jnp.float32)
PAGE = 8
# kind -> (kv_cache_dtype, ContinuousBatcher keywords)
KINDS = {
    "paged": (None, {"kv_page_size": PAGE}),
    "paged-int8": ("int8", {"kv_page_size": PAGE}),
    "dense": (None, {}),
    "speculative": (None, {"kv_page_size": PAGE, "speculative": True}),
}
CURSOR_KEYS = ("index", "length")


@pytest.fixture(scope="module")
def params():
    return tfm.TransformerLM(CFG).init(
        jax.random.PRNGKey(11), jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(kind, params, num_slots=3):
    kv_dtype, kwargs = KINDS[kind]
    kwargs = dict(kwargs)
    if kwargs.pop("speculative", False):
        # The target as its own draft: every draft is accepted, so a
        # round commits gamma + 1 tokens and the outputs are the
        # lockstep decoder's exactly.
        kwargs["speculative"] = serving.SpeculativeConfig(
            CFG, params, gamma=3)
    cfg = dataclasses.replace(CFG, kv_cache_dtype=kv_dtype)
    return serving.ContinuousBatcher(
        cfg, params, num_slots=num_slots, max_decode_len=96, **kwargs)


def _cursors(engine):
    """[(path, [slots] array)]: every layer's cursor leaf, of the
    draft's cache too."""
    caches = [engine.cache]
    if engine.speculative is not None:
        caches.append(engine._draft_cache)
    found = [(jax.tree_util.keystr(path), np.asarray(leaf))
             for path, leaf
             in jax.tree_util.tree_leaves_with_path(caches)
             if getattr(path[-1], "key", None) in CURSOR_KEYS]
    assert len(found) == CFG.n_layers * len(caches)
    return found


def _cached_tokens(engine):
    """Per slot what the host's books say the cache holds: every token
    of the request but the pending one (a token still in flight is
    one the device has, and the host has not), 0 for a slot without
    a request."""
    return np.asarray([
        0 if slot.request is None
        else (len(slot.request.prompt) + len(slot.generated)
              + slot.in_flight - 1)
        for slot in engine._slots])


def _requests(rng, count, prompt_len, new_tokens, name="r"):
    return [serving.Request(
        f"{name}{i}", [int(t) for t in rng.randint(1, 97, (prompt_len,))],
        max_new_tokens=int(new_tokens[i % len(new_tokens)]))
        for i in range(count)]


def _serve_alone(kind, params, request):
    engine = _engine(kind, params)
    engine.submit(serving.Request(request.request_id, request.prompt,
                                  request.max_new_tokens))
    done = {}
    while engine.pending():
        done.update(engine.step())
    return done[request.request_id]


def test_park_idle_cursors_touches_cursor_leaves_only():
    """The function itself: "length" / "index" leaves by the last key
    of their path, whatever else a layer keeps per slot."""
    active = jnp.asarray([True, False, True])
    cache = {
        "layer_0": {"attn": {"length": jnp.asarray([5, 6, 7]),
                             "block_table": jnp.ones((3, 4), jnp.int32),
                             "k_pages": jnp.ones((2, 8, 4))}},
        "layer_1": {"attn": {"index": jnp.asarray([9, 9, 9]),
                             "k": jnp.ones((3, 8, 2, 2))},
                    "mixer": {"state": jnp.full((3, 2), 3.0)}},
    }
    parked = inf._park_idle_cursors(cache, active)
    assert list(parked["layer_0"]["attn"]["length"]) == [5, 0, 7]
    assert list(parked["layer_1"]["attn"]["index"]) == [9, 0, 9]
    assert parked["layer_0"]["attn"]["length"].dtype == jnp.int32
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        if path[-1].key not in CURSOR_KEYS:
            same = parked
            for key in path:
                same = same[key.key]
            assert same is leaf, jax.tree_util.keystr(path)


def _watch_active(engine):
    """-> a list that receives the ``active`` vector each step program
    of ``engine`` is handed (as numpy, read before the call)."""
    seen = []
    name = "_decode_step" if engine.speculative is None else "_spec_step"
    inner = getattr(engine, name)

    def program(*args):
        seen.append(np.asarray(args[-2 if name == "_decode_step"
                                    else -1]))
        return inner(*args)

    setattr(engine, name, program)
    return seen


@pytest.mark.parametrize("kind", list(KINDS))
def test_idle_cursors_are_zero_and_live_ones_the_cached_tokens(
        kind, params):
    """After every step program of a run that seats, frees and
    re-seats slots, every cursor leaf of every layer reads 0 exactly
    where the program's ``active`` was false (a slot never used, one
    that has its last token) and the tokens the slot has cached where
    it was true. The serial (speculative) engine frees a slot after
    its last program ran, so it reads its old length until the next
    step parks it; the engine with a step in flight knows a finish by
    max_new_tokens beforehand, so the slot is idle in the very next
    program, which parks it in the call that frees it."""
    rng = np.random.RandomState(3)
    engine = _engine(kind, params)
    seen = _watch_active(engine)
    for req in _requests(rng, 5, 6, [3, 14, 30, 5, 9]):
        engine.submit(req)
    idle_checked = freed_then_parked = 0
    lagging: set = set()
    was_active: set = set()
    while engine.pending():
        programs = len(seen)
        engine.step()
        if len(seen) == programs:
            continue        # seated and finished at once: no program
        active = seen[-1]
        want = _cached_tokens(engine)
        held = np.asarray([s.request is not None for s in engine._slots])
        for path, leaf in _cursors(engine):
            assert not leaf[~active].any(), (path, leaf, active)
            assert (leaf[active] > 0).all(), (path, leaf, active)
            assert list(leaf[held]) == list(want[held]), (path, leaf)
        idle_checked += int((~active).sum())
        freed_then_parked += len(lagging - set(np.flatnonzero(active)))
        lagging = set(np.flatnonzero(active & ~held))
        if engine.speculative is None:
            assert not lagging
            freed_then_parked += len(was_active
                                     - set(np.flatnonzero(active)))
        was_active = set(np.flatnonzero(active))
    assert idle_checked >= 5 and freed_then_parked >= 2
    # stepped on with nothing live: _active is false everywhere, and
    # so is every cursor of every layer, in both caches
    assert lagging or engine.speculative is None
    engine.submit(_requests(rng, 1, 6, [2], name="last")[0])
    while engine.pending():
        engine.step()
    engine.submit(_requests(rng, 1, 6, [9], name="on")[0])
    engine.step()
    for path, leaf in _cursors(engine):
        assert list(leaf > 0) == list(np.asarray(engine._active)), path
        assert leaf.sum() == _cached_tokens(engine).sum()


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_long_request_is_served_alike_beside_forty_freed_ones(
        kind, params):
    """Greedy tokens of a request whose neighbours serve and free 40
    requests meanwhile, and of one seated afterwards in a slot that
    was parked, are a fresh engine's."""
    rng = np.random.RandomState(5)
    long = _requests(rng, 1, 7, [44], name="long")[0]
    churn = _requests(rng, 40, 5, [2, 3, 4], name="c")
    after = _requests(rng, 2, 6, [12, 7], name="after")
    engine = _engine(kind, params)
    for req in [long] + churn:
        engine.submit(serving.Request(req.request_id, req.prompt,
                                      req.max_new_tokens))
    done = {}
    long_steps = 0
    while engine.pending():
        done.update(engine.step())
        long_steps += long.request_id not in done
    assert len(done) == 41
    if kind != "speculative":
        # the churn ran beside the long request, not after it
        assert long_steps >= 40
    assert done[long.request_id] == _serve_alone(kind, params, long)
    # Every slot has served. A short request takes slot 0 and its
    # step parks the others; slot 0 itself keeps its length (no
    # program has run since it was freed). Two more requests take
    # slots 0 and 1: one seated in a parked slot, one in an unparked.
    flush = _requests(rng, 1, 6, [2], name="flush")[0]
    engine.submit(flush)
    while engine.pending():
        engine.step()
    assert {int(i) for _path, leaf in _cursors(engine)
            for i in np.flatnonzero(leaf)} == {0}
    for req in after:
        engine.submit(serving.Request(req.request_id, req.prompt,
                                      req.max_new_tokens))
    while engine.pending():
        done.update(engine.step())
    for req in after:
        assert done[req.request_id] == _serve_alone(kind, params, req)


# kind -> (config fields, engine keywords, the decode road): every
# paged kind of engine, on the CPU's gathers and, where a kernel
# serves the pool's pages, on the kernel in interpret mode (fewer
# slots there: the interpreter runs a program a slot a layer a step)
_BESIDE = {
    "paged": ({}, {}, None),
    "paged-int8": ({"kv_cache_dtype": "int8"}, {}, None),
    "grouped": ({"n_kv_heads": 1}, {}, None),
    "window": ({"n_kv_heads": 1, "layer_windows": (0, 12)}, {}, None),
    "speculative-grouped": ({"n_kv_heads": 1}, {"speculative": True},
                            None),
    "paged-kernel": ({}, {}, "kernel"),
    "paged-int8-kernel": ({"kv_cache_dtype": "int8"}, {}, "kernel"),
    "window-kernel": ({"n_kv_heads": 1, "layer_windows": (0, 12)}, {},
                      "kernel"),
}


@pytest.mark.parametrize("kind", sorted(_BESIDE))
def test_two_requests_are_served_alike_beside_forty_parked_slots(
        kind):
    """Greedy tokens of two requests in an engine of 42 slots (ten, or
    six, where a kernel runs in interpret mode), forty of
    which never hold a request and are handed to every layer's paged
    decode call at length 0 (zeros, on every road), are those of an
    engine of just two slots, where no slot is ever parked while both
    decode. One of the two is seated again after the other has ended,
    in a slot that was parked meanwhile."""
    from jax.experimental.pallas import tpu as pltpu

    fields, kwargs, impl = _BESIDE[kind]
    cfg = dataclasses.replace(CFG, paged_attention_impl=impl, **fields)
    weights = tfm.TransformerLM(cfg).init(
        jax.random.PRNGKey(11), jnp.zeros((1, 8), jnp.int32))["params"]
    kwargs = dict(kwargs, kv_page_size=PAGE)
    if kwargs.pop("speculative", False):
        kwargs["speculative"] = serving.SpeculativeConfig(
            dataclasses.replace(cfg, paged_attention_impl=None),
            weights, gamma=2)
    rng = np.random.RandomState(13)
    first = _requests(rng, 2, 9, [5, 14], name="a")
    second = _requests(rng, 1, 6, [6], name="b")

    def serve(num_slots):
        engine = serving.ContinuousBatcher(
            cfg, weights, num_slots=num_slots, max_decode_len=96,
            **kwargs)
        done = {}
        for batch in (first, second):
            for req in batch:
                engine.submit(serving.Request(
                    req.request_id, req.prompt, req.max_new_tokens))
            while engine.pending():
                done.update(engine.step())
        return done

    # (the int8 pages' kernel is a program a (slot, table entry): six
    # slots there, four of them parked)
    slots = 42 if impl is None else 6 if "int8" in kind else 10
    if impl is None:
        crowded, alone = serve(slots), serve(2)
    else:
        with pltpu.force_tpu_interpret_mode():
            crowded, alone = serve(slots), serve(2)
    assert sorted(crowded) == ["a0", "a1", "b0"]
    assert crowded == alone
    assert [len(crowded[r]) for r in ("a0", "a1", "b0")] == [5, 14, 6]


def _watch_handed(engine):
    """-> a list that receives, as each decode step program of
    ``engine`` is called: the ``active`` mask it is handed, the
    device's cursors of the first layer (the row the step writes not
    yet counted: the kernel is handed cursor + 1 under the mask) and
    occupancy() at that moment."""
    seen = []
    inner = engine._decode_step

    def program(params_, cache, tokens, positions, active, key):
        seen.append((np.asarray(active),
                     np.asarray(cache["layer_0"]["attn"]["length"]),
                     engine.occupancy()))
        return inner(params_, cache, tokens, positions, active, key)

    engine._decode_step = program
    return seen


@pytest.mark.parametrize("kind", ["paged", "paged-int8"])
def test_kv_blocks_attended_is_the_kernels_count(kind, params):
    """occupancy()'s kv_blocks_attended, from the host's books, against
    what the decode kernel is handed, read as each step program is
    called: the device's own cursors (the row the step writes counted:
    cursor + 1) under the ``active`` mask the program receives, a slot
    it leaves out counting nothing whatever its cursor reads (one
    without a request; one whose last token is in flight: its request
    still seated, its cursor still at its old length, the mask already
    false). Equal at EVERY step: sum(ceil(tokens / page)) over the
    seated slots."""
    rng = np.random.RandomState(9)
    engine = _engine(kind, params, num_slots=4)
    seen = _watch_handed(engine)
    for req in _requests(rng, 6, 11, [4, 19, 33, 7]):
        engine.submit(req)
    while engine.pending():
        engine.step()
    idle = freed_unparked = 0
    for active, length, state in seen:
        kernel = np.where(active, -(-(length + 1) // PAGE), 0)
        assert state["kv_blocks_attended"] == int(kernel.sum())
        assert state["slots_active"] == int(active.sum())
        assert state["kv_blocks_attended"] >= -(
            -state["live_tokens"] // PAGE)
        idle += int((~active).sum())
        freed_unparked += int((length[~active] > 0).sum())
    assert len(seen) >= 20 and idle >= 20 and freed_unparked >= 3
    assert engine.occupancy()["kv_blocks_attended"] == 0
    assert "kv_blocks_attended" not in _engine(
        "dense", params).occupancy()


@pytest.mark.parametrize("kind", ["paged", "paged-kernel",
                                  "window-kernel"])
def test_first_chunks_prefetched_is_the_kernels_hand_over_count(kind):
    """occupancy()'s kv_first_chunks_prefetched against the lengths the
    decode kernel is handed, read as each step program is called (the
    device's cursors + 1 under the program's ``active`` mask): one
    less than the slots of length > 0, never under 0, at EVERY step
    of a run that seats, finishes and re-seats requests with parked
    slots before, between and behind the seated ones. On the kernel
    (interpret mode) every seated slot but the first takes its first
    chunk from the seated slot before it, across the parked programs
    between, and the tokens are those each request is served ALONE
    (one seated slot: it starts its own first chunk, as every slot
    did until PR 48)."""
    from jax.experimental.pallas import tpu as pltpu

    fields, kwargs, impl = _BESIDE[kind]
    cfg = dataclasses.replace(CFG, paged_attention_impl=impl, **fields)
    weights = tfm.TransformerLM(cfg).init(
        jax.random.PRNGKey(11), jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.RandomState(17)
    requests = _requests(rng, 6, 9, [2, 25, 3, 14, 4, 6])

    def serve(batch, watch=False):
        engine = serving.ContinuousBatcher(
            cfg, weights, num_slots=5, max_decode_len=96,
            kv_page_size=PAGE, **kwargs)
        seen = _watch_handed(engine) if watch else []
        for req in batch:
            engine.submit(serving.Request(
                req.request_id, req.prompt, req.max_new_tokens))
        done = {}
        while engine.pending():
            done.update(engine.step())
        assert engine.occupancy()["kv_first_chunks_prefetched"] == 0
        return done, seen

    with (pltpu.force_tpu_interpret_mode() if impl
          else contextlib.nullcontext()):
        together, seen = serve(requests, watch=True)
        if impl:
            for req in requests[1:3]:
                assert serve([req])[0][req.request_id] == together[
                    req.request_id]
    assert sorted(together) == sorted(r.request_id for r in requests)
    handed_on = passed_on = 0
    for active, length, state in seen:
        handed = np.where(active, length + 1, 0)
        seated = np.flatnonzero(handed > 0)
        assert state["kv_first_chunks_prefetched"] == max(
            len(seated) - 1, 0)
        assert state["kv_first_chunks_prefetched"] == max(
            state["slots_active"] - 1, 0)
        handed_on += max(len(seated) - 1, 0)
        # parked programs between two seated ones pass a fetch on
        passed_on += len(seated) > 1 and (
            seated[-1] - seated[0] + 1 > len(seated))
    assert len(seen) >= 20 and handed_on >= 20 and passed_on >= 10


def test_inactive_slot_probe_tiny_path():
    """tools/inactive_slot_probe.py --tiny: the after-picture's control
    flow off the chip (no timing is read): cursors set behind the
    engine's back are back at 0 one step later, whatever they were."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)      # one device, as on the one-chip box
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools/inactive_slot_probe.py"),
         "--tiny"], capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    rows = [json.loads(line)
            for line in proc.stdout.strip().splitlines()]
    cases, verdict = rows[:-1], rows[-1]
    assert [row["idle_cursors_set_to"] for row in cases] == [
        0, 128, 256, 0]
    assert all(row["idle_cursor_max_one_step_later"] == 0
               for row in cases)
    # three idle slots of four: whatever their cursors were set to,
    # the step's mask hands them to the kernel at length 0, so the
    # first step attends over the seated slot's blocks alone, as every
    # later one does
    for row in cases:
        assert (row["first_step_kv_blocks"]
                == row["kv_blocks_attended"] > 0), row
    assert verdict["idle_cursors_parked"] is True
    assert (verdict["live_slots"], verdict["idle_slots"]) == (1, 3)

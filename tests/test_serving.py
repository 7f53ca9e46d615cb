"""Continuous batching engine: greedy equivalence with the lockstep
generator, slot reuse, early-eos, and per-slot cache isolation."""

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


def test_continuous_batching_matches_lockstep(params):
    """5 requests with different prompt lengths through a 2-slot
    engine produce EXACTLY the tokens batch-1 greedy decoding
    produces for each — slots at different depths don't interfere."""
    rng = np.random.RandomState(0)
    requests = [
        serving.Request(f"r{i}", list(rng.randint(0, 97, (3 + i,))),
                        max_new_tokens=4 + (i % 3))
        for i in range(5)
    ]
    engine = serving.ContinuousBatcher(CFG, params, num_slots=2,
                                       max_decode_len=64)
    for req in requests:
        engine.submit(req)
    results = {}
    for _ in range(200):
        for rid, toks in engine.step():
            results[rid] = toks
        if not engine.pending():
            break
    assert set(results) == {r.request_id for r in requests}
    for req in requests:
        want = reference_greedy(params, req.prompt, req.max_new_tokens)
        assert results[req.request_id] == want, (
            req.request_id, results[req.request_id], want)


def test_eos_frees_slot_early(params):
    """A request whose first sampled token is its eos finishes in one
    step and its slot is immediately reused."""
    rng = np.random.RandomState(1)
    prompt = list(rng.randint(0, 97, (4,)))
    first = reference_greedy(params, prompt, 1)[0]
    engine = serving.ContinuousBatcher(CFG, params, num_slots=1,
                                       max_decode_len=64)
    engine.submit(serving.Request("eos", prompt, max_new_tokens=10,
                                  eos_id=first))
    other = list(rng.randint(0, 97, (5,)))
    engine.submit(serving.Request("next", other, max_new_tokens=3))
    results = {}
    for _ in range(50):
        for rid, toks in engine.step():
            results[rid] = toks
        if not engine.pending():
            break
    assert results["eos"] == [first]
    assert results["next"] == reference_greedy(params, other, 3)


def test_submit_rejects_overflow(params):
    engine = serving.ContinuousBatcher(CFG, params, num_slots=1,
                                       max_decode_len=16)
    with pytest.raises(ValueError, match="exceeds max_decode_len"):
        engine.submit(serving.Request("big", [1] * 10,
                                      max_new_tokens=10))


def test_paged_engine_matches_dense(params):
    """The paged KV cache (block tables over a shared page pool)
    produces exactly the dense engine's greedy outputs, including
    prompts that are exact page multiples and generations that cross
    page boundaries."""
    rng = np.random.RandomState(2)
    requests = [
        serving.Request("p0", list(rng.randint(0, 97, (8,))),  # =page
                        max_new_tokens=9),                     # cross
        serving.Request("p1", list(rng.randint(0, 97, (3,))),
                        max_new_tokens=6),
        serving.Request("p2", list(rng.randint(0, 97, (13,))),
                        max_new_tokens=4),
    ]
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=64, kv_page_size=8)
    for r in requests:
        engine.submit(serving.Request(r.request_id, r.prompt,
                                      r.max_new_tokens))
    results = {}
    for _ in range(200):
        for rid, toks in engine.step():
            results[rid] = toks
        if not engine.pending():
            break
    for r in requests:
        want = reference_greedy(params, r.prompt, r.max_new_tokens)
        assert results[r.request_id] == want, (r.request_id,
                                               results[r.request_id],
                                               want)


def test_paged_pool_overcommit_admission_waits(params):
    """With a page pool smaller than slots*max_len, admission waits
    for frees instead of deadlocking; pages are recycled across
    requests and everything completes."""
    rng = np.random.RandomState(3)
    reqs = [serving.Request(f"o{i}", list(rng.randint(0, 97, (8,))),
                            max_new_tokens=6) for i in range(4)]
    # 3 pages of 8 = 24 tokens total: one request (8+6 tokens -> 2
    # pages) fits; two concurrent would need 4.
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=32, kv_page_size=8,
        kv_num_pages=3)
    for r in reqs:
        engine.submit(r)
    results = {}
    for _ in range(400):
        for rid, toks in engine.step():
            results[rid] = toks
        if not engine.pending():
            break
    assert set(results) == {r.request_id for r in reqs}
    for r in reqs:
        assert results[r.request_id] == reference_greedy(
            params, r.prompt, r.max_new_tokens)
    # All pages reclaimable after drain: free, or parked unreferenced
    # in the prefix-cache LRU (indexed for reuse, evictable on
    # demand) — none pinned.
    engine.pages.check()
    occupancy = engine.occupancy()
    assert occupancy["kv_pages_in_use"] == 0
    assert (occupancy["kv_pages_free"]
            + occupancy["kv_pages_lru"]) == 3


def test_paged_freed_slot_cannot_corrupt_recycled_pages(params):
    """Regression: a freed slot keeps decoding (masked) in the full
    batch; its stale block table must not scribble over pages that
    were returned to the pool and reallocated to a still-active slot.
    r0 finishes early mid-page; r1 keeps generating across page
    boundaries using recycled pages; r1's output must stay exactly
    equal to the reference."""
    rng = np.random.RandomState(4)
    p0 = list(rng.randint(0, 97, (5,)))
    p1 = list(rng.randint(0, 97, (6,)))
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=48, kv_page_size=8,
        kv_num_pages=6)
    engine.submit(serving.Request("r0", p0, max_new_tokens=2))
    engine.submit(serving.Request("r1", p1, max_new_tokens=30))
    results = {}
    for _ in range(100):
        for rid, toks in engine.step():
            results[rid] = toks
        if not engine.pending():
            break
    assert results["r0"] == reference_greedy(params, p0, 2)
    assert results["r1"] == reference_greedy(params, p1, 30)


def test_paged_submit_rejects_unadmittable(params):
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=32, kv_page_size=8,
        kv_num_pages=3)
    with pytest.raises(ValueError, match="could never admit"):
        engine.submit(serving.Request("huge", [1] * 20,
                                      max_new_tokens=12))


def test_prefill_buckets_bound_compiles():
    """Prompts of different lengths inside one power-of-two bucket
    share a single prefill compilation; a longer prompt crossing into
    the next bucket adds exactly one more. The prefill jit is
    module-level (same-config engines share compiles), so measure
    CACHE-SIZE DELTAS with a config unique to this test."""
    ucfg = tfm.TransformerConfig(
        vocab_size=101, d_model=32, n_layers=2, n_heads=2, d_head=16,
        d_ff=64, max_seq_len=64, dtype=jnp.float32,
        param_dtype=jnp.float32)
    uparams = tfm.TransformerLM(ucfg).init(
        jax.random.PRNGKey(5), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = serving.ContinuousBatcher(ucfg, uparams, num_slots=4,
                                    max_decode_len=64)
    base = serving._prefill_dense._cache_size()
    for rid, n in (("a", 3), ("b", 5), ("c", 11)):   # bucket 16
        eng.submit(serving.Request(rid, [7] * n, max_new_tokens=2))
    done = []
    for _ in range(30):
        done += eng.step()
        if len(done) == 3:
            break
    assert len(done) == 3
    assert serving._prefill_dense._cache_size() == base + 1
    eng.submit(serving.Request("d", [7] * 20, max_new_tokens=2))
    for _ in range(30):
        done += eng.step()
        if len(done) == 4:
            break
    assert len(done) == 4
    assert serving._prefill_dense._cache_size() == base + 2


def test_paged_prefill_bucket_shorter_than_page(params):
    """A prompt whose bucket is smaller than the page size still
    writes its (single, partial) page correctly: greedy output equals
    the dense engine's."""
    prompt = [5, 9, 2]                     # bucket 16 < page 32
    dense = serving.ContinuousBatcher(CFG, params, num_slots=2,
                                      max_decode_len=64)
    paged = serving.ContinuousBatcher(CFG, params, num_slots=2,
                                      max_decode_len=64,
                                      kv_page_size=32)
    outs = []
    for eng in (dense, paged):
        eng.submit(serving.Request("r", prompt, max_new_tokens=6))
        got = []
        for _ in range(20):
            got += eng.step()
            if got:
                break
        outs.append(got[0][1])
    assert outs[0] == outs[1], outs


def test_int8_quantized_serving_generates(params):
    """ROADMAP 'int8 serving via QuantDense': a quantize_matmuls
    config runs the whole continuous-batching path on the int8
    kernels (interpret mode here; MXU int8 on hardware)."""
    from jax.experimental.pallas import tpu as pltpu
    qcfg = tfm.TransformerConfig(
        vocab_size=97, d_model=128, n_layers=1, n_heads=2, d_head=64,
        d_ff=128, dtype=jnp.float32, param_dtype=jnp.float32,
        quantize_matmuls=True)
    with pltpu.force_tpu_interpret_mode():
        qparams = tfm.TransformerLM(qcfg).init(
            jax.random.PRNGKey(1),
            jnp.zeros((1, 8), jnp.int32))["params"]
        eng = serving.ContinuousBatcher(qcfg, qparams, num_slots=2,
                                        max_decode_len=32)
        eng.submit(serving.Request("q", [5, 9], max_new_tokens=3))
        done = []
        for _ in range(10):
            done += eng.step()
            if done:
                break
    assert done and len(done[0][1]) == 3


def test_overcommit_preemption_matches_greedy(params):
    """Force preemptions (pool far below aggregate worst case, long
    generations, no eos): victims are evicted mid-decode, re-queued,
    and resumed via re-prefill of prompt+generated — final outputs
    must STILL match uninterrupted batch-1 greedy decoding exactly."""
    rng = np.random.RandomState(5)
    reqs = [serving.Request(f"p{i}", list(rng.randint(0, 97, (6,))),
                            max_new_tokens=18) for i in range(4)]
    # Worst case per request: ceil((6+18)/8) = 3 pages; aggregate 12.
    # 5 pages forces decode-time exhaustion while both slots run.
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=32, kv_page_size=8,
        kv_num_pages=5, overcommit=True)
    for r in reqs:
        engine.submit(r)
    results = {}
    for _ in range(600):
        for rid, toks in engine.step():
            results[rid] = toks
        if not engine.pending():
            break
    assert set(results) == {r.request_id for r in reqs}
    assert engine.preemptions > 0, \
        "scenario failed to exercise preemption"
    for r in reqs:
        assert results[r.request_id] == reference_greedy(
            params, r.prompt, r.max_new_tokens), r.request_id
    engine.pages.check()
    occupancy = engine.occupancy()
    assert occupancy["kv_pages_in_use"] == 0
    assert (occupancy["kv_pages_free"]
            + occupancy["kv_pages_lru"]) == 5


def test_overcommit_beats_reservation_when_generations_are_short():
    """The overcommit win: requests DECLARE worst-case max_new_tokens
    but actually finish after a couple of tokens (eos). Reservation
    admission serializes them (each reserves the whole pool);
    overcommit runs them concurrently — strictly fewer engine steps,
    identical outputs, zero preemptions needed."""
    model = tfm.TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(7),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    rng = np.random.RandomState(9)
    prompts = [list(rng.randint(0, 97, (4,))) for _ in range(4)]
    # Discover each prompt's 2nd greedy token and use it as that
    # request's eos: every request really finishes after 2 tokens.
    eos = {i: reference_greedy(params, p, 2)[-1]
           for i, p in enumerate(prompts)}

    def run(overcommit):
        engine = serving.ContinuousBatcher(
            CFG, params, num_slots=4, max_decode_len=32,
            kv_page_size=8, kv_num_pages=4, overcommit=overcommit)
        for i, p in enumerate(prompts):
            engine.submit(serving.Request(
                f"s{i}", p, max_new_tokens=24, eos_id=eos[i]))
        results, steps = {}, 0
        for _ in range(400):
            steps += 1
            for rid, toks in engine.step():
                results[rid] = toks
            if not engine.pending():
                break
        return results, steps, engine.preemptions

    res_r, steps_r, _ = run(overcommit=False)
    res_o, steps_o, preempts = run(overcommit=True)
    assert res_r == res_o
    assert set(res_o) == {f"s{i}" for i in range(4)}
    # Each request: prompt 4 + worst 24 = 28 tokens = 4 pages — the
    # whole pool, so reservation admits ONE at a time (4 sequential
    # waves); overcommit admits all four at once.
    assert steps_o < steps_r, (steps_o, steps_r)
    assert preempts == 0


def test_admission_priority_orders_the_wait_line(params):
    """Queued requests admit in priority order (FIFO within a class);
    active slots are never preempted for priority."""
    engine = serving.ContinuousBatcher(CFG, params, num_slots=1,
                                       max_decode_len=64)
    engine.submit(serving.Request("first", [1, 2],
                                  max_new_tokens=8))
    engine.step()  # 'first' occupies the single slot
    engine.submit(serving.Request("low-a", [3], max_new_tokens=1))
    engine.submit(serving.Request("low-b", [4], max_new_tokens=1))
    engine.submit(serving.Request("hi", [5], max_new_tokens=1,
                                  priority=9))
    order = []
    for _ in range(40):
        for request_id, _tokens in engine.step():
            order.append(request_id)
        if len(order) == 4:
            break
    assert order[0] == "first"          # never preempted
    assert order[1] == "hi"             # overtakes the queue
    assert order[2:] == ["low-a", "low-b"]  # FIFO within class


def test_preempted_victim_resumes_within_its_priority_class(params):
    """A preempted low-priority request resumes ahead of its peers
    but never ahead of a queued HIGHER-priority request."""
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=64,
        kv_page_size=4, kv_num_pages=4, overcommit=True)
    engine.submit(serving.Request("low1", [1, 2],
                                  max_new_tokens=12))
    engine.submit(serving.Request("low2", [3, 4],
                                  max_new_tokens=12))
    engine.step()  # both lows admit and start decoding
    engine.submit(serving.Request("hi", [5], max_new_tokens=1,
                                  priority=5))
    order = []
    for _ in range(200):
        for request_id, _tokens in engine.step():
            order.append(request_id)
        if len(order) == 3:
            break
    assert engine.preemptions >= 1, order  # page pressure DID preempt
    # The high-priority request admitted into the freed capacity
    # before the preempted low resumed.
    assert order[0] == "hi", (order, engine.preemptions)
    assert set(order[1:]) == {"low1", "low2"}


def test_chunked_prefill_greedy_equivalent(params):
    """prefill_chunk: chunked multi-token inserts with global RoPE
    positions must produce tokens identical to the one-pass prefill
    (dense and paged engines), while bounding prefill memory."""
    prompt = [5, 17, 31, 2, 9, 40, 11, 3, 8, 22, 7, 19, 28, 33,
              41, 6, 13, 2, 55, 60, 61, 44]  # 22 tokens -> 32 bucket

    def run(prefill_chunk, page=None):
        engine = serving.ContinuousBatcher(
            CFG, params, num_slots=2, max_decode_len=64,
            kv_page_size=page, prefill_chunk=prefill_chunk)
        engine.submit(serving.Request("r", list(prompt),
                                      max_new_tokens=10))
        out = None
        while engine.pending():
            for _rid, tokens in engine.step():
                out = tokens
        return out

    for page in (None, 16):
        ref = run(None, page)
        for chunk in (8, 16):
            got = run(chunk, page)
            assert got == ref, (page, chunk, got, ref)


# ----------------------- step tracing (serve_step) ----------------------

import os  # noqa: E402

from batch_shipyard_tpu.trace import spans as trace_spans  # noqa: E402


def _traced_engine(kind, params):
    kwargs = {
        "dense": {},
        "paged": {"kv_page_size": 8, "kv_num_pages": 16,
                  "prefix_cache": False},
        "prefix-shared": {"kv_page_size": 8, "kv_num_pages": 24},
        "speculative": {"speculative": serving.SpeculativeConfig(
            CFG, params, gamma=2)},
    }[kind]
    return serving.ContinuousBatcher(CFG, params, num_slots=2,
                                     max_decode_len=64, **kwargs)


def _shared_prefix_requests(count=4):
    rng = np.random.RandomState(5)
    prefix = list(rng.randint(1, 97, (16,)))    # two whole pages
    return [serving.Request(
        f"r{i}", prefix + list(rng.randint(1, 97, (3 + i,))),
        max_new_tokens=3 + i) for i in range(count)]


def _outside_view(engine):
    """What benchmark/drivers/serve.py::StepRecorder reads as a step
    starts, from the engine's private lists: the slots the call's
    decode step advances (a request whose last token is in flight
    holds its slot and is not one) and the tokens they attend over,
    the one in flight included."""
    decoding = [slot for slot in engine._slots
                if slot.request is not None
                and len(slot.generated) + slot.in_flight
                < slot.request.max_new_tokens]
    view = {
        "slots_active": len(decoding),
        "queued": len(engine._queue),
        "live_tokens": sum(
            len(slot.request.prompt) + len(slot.generated)
            + slot.in_flight for slot in decoding)}
    if engine.paged:
        table = engine.pages.table
        view["kv_pages_in_use"] = len(
            set(table.ravel()) - {engine.pages.scratch_page})
    return view


def _drain_traced(engine, requests):
    for req in requests:
        engine.submit(req)
    results, views = {}, []
    for _ in range(200):
        if not engine.pending():
            break
        views.append(_outside_view(engine))
        for rid, toks in engine.step():
            results[rid] = toks
    return results, views


@pytest.mark.parametrize(
    "kind", ["dense", "paged", "prefix-shared", "speculative"])
def test_every_step_writes_one_row_that_agrees_with_the_private_lists(
        kind, params, recorder):
    engine = _traced_engine(kind, params)
    requests = _shared_prefix_requests()
    results, views = _drain_traced(engine, requests)
    rows = recorder()
    assert [r["kind"] for r in rows] == ["serve_step"] * len(views)
    assert engine.steps_total == len(views)
    for row, view in zip(rows, views):
        attrs = row["attrs"]
        assert row["parent_span_id"] == "run-1"
        assert {k: attrs[k] for k in view} == view
        assert attrs["slots_total"] == 2
        phase_ms = sum(attrs[f"{name}_ms"]
                       for name in serving.STEP_PHASES)
        assert 0 < phase_ms <= (row["end"] - row["start"]) * 1e3 + 1e-6
        assert attrs["prefills"] == len(attrs["admitted"])
    if engine.paged:
        # the first chunks the step's decode kernel hands on: one
        # less than the slots the row found decoding
        assert all(r["attrs"]["kv_first_chunks_prefetched"]
                   == max(r["attrs"]["slots_active"] - 1, 0)
                   for r in rows)
        assert any(r["attrs"]["kv_first_chunks_prefetched"]
                   for r in rows) or kind == "speculative"
        assert all(r["attrs"]["kv_pages_total"] == engine.pages.num_pages
                   and r["attrs"]["kv_pages_free"]
                   + r["attrs"]["kv_pages_lru"]
                   + r["attrs"]["kv_pages_in_use"]
                   <= engine.pages.num_pages for r in rows)
    else:
        assert "kv_pages_in_use" not in rows[0]["attrs"]
        assert "kv_first_chunks_prefetched" not in rows[0]["attrs"]
    admitted = [a for r in rows for a in r["attrs"]["admitted"]]
    assert sorted(a["request_id"] for a in admitted) == \
        sorted(r.request_id for r in requests)
    paths = {a["path"] for a in admitted}
    assert paths == {"dense": {"dense"}, "paged": {"cold"},
                     "prefix-shared": {"cold", "shared"},
                     "speculative": {"dense"}}[kind]
    for entry in admitted:
        assert entry["bucket"] >= entry["tokens"] > 0
    # what the calls landed: every prefill's first token and, a decode
    # launch, one token a slot it advanced (a speculative round may
    # commit more, or tokens past a request's end)
    landed = [x for r in rows for x in r["attrs"]["landed"]]
    assert sum(x["tokens"] for x in landed
               if x["kind"] == "prefill") == \
        sum(a["tokens"] for a in admitted)
    served = sum(len(toks) for toks in results.values())
    emitted = sum(x.get("rows", 1) for x in landed)
    assert emitted == served if kind != "speculative" \
        else emitted <= served
    assert sum(r["attrs"]["finished"] for r in rows) == len(requests)
    assert rows[-1]["attrs"]["readback_ms"] > 0


@pytest.mark.parametrize("kind", ["dense", "prefix-shared"])
def test_recorder_off_writes_nothing_and_changes_no_token(
        kind, params, recorder, monkeypatch):
    traced, _ = _drain_traced(_traced_engine(kind, params),
                              _shared_prefix_requests())
    n_rows = len(recorder())
    assert n_rows > 0
    monkeypatch.delenv("SHIPYARD_TRACE_FILE")
    engine = _traced_engine(kind, params)
    plain, views = _drain_traced(engine, _shared_prefix_requests())
    assert plain == traced
    monkeypatch.setenv("SHIPYARD_TRACE_FILE", os.devnull)
    assert trace_spans.flush() == 0         # nothing was buffered
    # the cumulative counters are always on
    stats = engine.step_stats()
    assert stats["steps"] == len(views) and engine.traced_steps == 0
    assert set(stats["phase_seconds"]) == set(serving.STEP_PHASES)
    assert 0 < sum(stats["phase_seconds"].values()) <= \
        stats["step_seconds"]


def test_a_step_that_compiles_says_so_in_its_row(recorder):
    """A width no other test uses: its first step has to build (or
    load) its prefill and decode programs."""
    cfg = tfm.TransformerConfig(
        vocab_size=97, d_model=24, n_layers=1, n_heads=2, d_head=12,
        d_ff=40, max_seq_len=64, dtype=jnp.float32,
        param_dtype=jnp.float32)
    odd = tfm.TransformerLM(cfg).init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32))["params"]
    engine = serving.ContinuousBatcher(cfg, odd, num_slots=3,
                                       max_decode_len=64)
    before = engine.step_stats()["compiles"]
    engine.submit(serving.Request("c", [1, 2, 3], max_new_tokens=4))
    while engine.pending():
        engine.step()
    rows = recorder()
    assert rows[0]["attrs"]["compiles"] >= 1
    assert rows[0]["attrs"]["compile_ms"] > 0
    assert "compiles" not in rows[-1]["attrs"]
    assert engine.step_stats()["compiles"] >= \
        before + rows[0]["attrs"]["compiles"]


def test_step_rows_are_head_sampled_and_the_counters_are_not(
        params, recorder, monkeypatch):
    monkeypatch.setattr(serving.ContinuousBatcher, "_STEP_HEAD", 3)
    monkeypatch.setattr(serving.ContinuousBatcher,
                        "_STEP_SAMPLE_EVERY", 4)
    engine = _traced_engine("dense", params)
    engine.submit(serving.Request("long", [5, 6, 7],
                                  max_new_tokens=14))
    steps = 0
    while engine.pending():
        engine.step()
        steps += 1
    # 13 decode steps, and a 14th call that only reads the last back
    assert steps == 14 == engine.steps_total == engine.traced_steps
    assert engine.step_stats()["decode_steps"] == 13
    rows = recorder()
    # steps 1-3 in full, then every fourth: 4, 8, 12. A step adds a
    # token; the first adds the prefill's too (3 + 2 live at step 2,
    # the token in flight counted).
    assert [r["attrs"]["live_tokens"] for r in rows] == \
        [0, 5, 6, 7, 11, 15]
    # an engine with nothing to seat or decode writes nothing
    engine.step()
    assert engine.steps_total == 14 and len(recorder()) == 6


# ------------- the first token, sampled and seated on the device ---------

@pytest.mark.parametrize("sampling", [
    inf.SamplingConfig(),
    inf.SamplingConfig(temperature=0.9, top_k=20),
    inf.SamplingConfig(temperature=1.3)])
def test_the_seat_program_is_the_eager_sample_and_scatters(sampling):
    """serving._seat_first against the eager ops it replaces: the key
    split as _admit split it, the same token from the same sample key,
    the same two scatters; the slot and the length are traced, so one
    compilation serves every slot."""
    rng = np.random.RandomState(3)
    tokens = jnp.asarray(rng.randint(0, 97, (5, 1)), jnp.int32)
    positions = jnp.asarray(rng.randint(0, 40, (5,)), jnp.int32)
    key = jax.random.PRNGKey(11)
    before = serving._seat_first._cache_size()
    for slot, length in ((3, 17), (0, 4), (4, 33)):
        logits = jnp.asarray(rng.randn(97), jnp.float32)
        want_key, sample_key = jax.random.split(key)
        want = inf._sample(logits[None], sample_key, sampling)
        key, tokens_out, positions_out, first = serving._seat_first(
            sampling, logits, key, tokens, positions, slot, length)
        np.testing.assert_array_equal(key, want_key)
        np.testing.assert_array_equal(first, want)
        assert first.shape == (1,) and first.dtype == jnp.int32
        np.testing.assert_array_equal(
            tokens_out, tokens.at[slot, 0].set(want[0]))
        np.testing.assert_array_equal(
            positions_out, positions.at[slot].set(length))
        tokens, positions = tokens_out, positions_out
    assert serving._seat_first._cache_size() == before + 1


def test_the_speculative_engine_lands_first_tokens_before_its_step(
        params):
    """No step is ever in flight with a draft model: the first tokens
    of a call's admissions land right after _admit, through the same
    code, before the serial draft/verify round, and nothing is unread
    between calls."""
    rng = np.random.RandomState(5)
    requests = [
        serving.Request(f"s{i}", list(rng.randint(0, 97, (4 + 3 * i,))),
                        max_new_tokens=new)
        for i, new in enumerate((7, 1, 5))]
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=2, max_decode_len=64,
        speculative=serving.SpeculativeConfig(CFG, params, gamma=2))
    landed: list = []
    land_first = engine._land_first

    def watched(first):
        landed.append((engine._slots[first.slot].request.request_id,
                       engine.spec_rounds))
        return land_first(first)

    engine._land_first = watched
    for req in requests:
        engine.submit(req)
    results = {}
    for _ in range(50):
        for rid, toks in engine.step():
            results[rid] = toks
        assert not engine._unread
        if not engine.pending():
            break
    for req in requests:
        assert results[req.request_id] == reference_greedy(
            params, req.prompt, req.max_new_tokens)
    # s0 and s1 in the first call, before any round; s2 takes the
    # slot s1's one token freed, a call later
    assert [rid for rid, _ in landed] == ["s0", "s1", "s2"]
    assert [rounds for _, rounds in landed][:2] == [0, 0]
    stats = engine.step_stats()
    assert stats["decode_steps"] == stats["steps_overlapped"] == 0
    assert (stats["prefills"], stats["prefills_overlapped"]) == (3, 1)
    assert not any(stats["settles"].values())
    assert stats["overshoot_tokens"] == 0

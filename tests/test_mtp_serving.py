"""A model that carries its own drafter (a multi-token-prediction
module, TransformerConfig.mtp_modules) through the continuous batcher:
every decode step verifies the module's draft at two positions, lands
one or two tokens a slot on the lookahead's step order, and is
LOSSLESS: the tokens are the ones the same engine lands with the
module left out. Beside it: the grouped paged-decode kernel at several
query positions a slot (pool and ring, interpret mode), the dense
layer kind and the q/k norms, the books (tokens in flight as a count,
page growth, preemption, max_new_tokens inside a landing), the
counters and what take_decisions hands over."""

import contextlib
import dataclasses
import http.client
import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.models import inference as inf
from batch_shipyard_tpu.models import moe, serving
from batch_shipyard_tpu.models import transformer as tfm
from batch_shipyard_tpu.ops import attention as attn_ops
from batch_shipyard_tpu.ops import paged_attention as pa

D, VOCAB, WINDOW, PAGE = 32, 64, 8, 4
# a leading dense layer, then window, full and window layers over
# routed experts: each published layer two blocks
KINDS = ("attn", "mlp", "attn", "experts", "attn", "experts", "attn",
         "experts")
WINDOWS = (WINDOW, 0, WINDOW, 0, 0, 0, WINDOW, 0)
ROPES = tuple(bool(w) for w in WINDOWS)


def _config(mtp=1, routed=True, windows=WINDOWS, **over):
    experts = moe.RoutedConfig(
        d_model=D, n_experts=8, top_k=2, d_expert=16, d_shared=16,
        scale=2.5, experts_held=4, gated=True, scoring="sigmoid")
    kinds = KINDS if routed else tuple(
        "mlp" if kind == "experts" else kind for kind in KINDS)
    return tfm.TransformerConfig(
        vocab_size=VOCAB, d_model=D, n_layers=len(kinds), n_heads=4,
        n_kv_heads=2, d_head=8, d_ff=48, max_seq_len=128,
        dtype=jnp.float32, param_dtype=jnp.float32,
        tie_embeddings=False, norm_eps=1e-5, block_kinds=kinds,
        layer_windows=windows, layer_rope=ROPES, qk_norm=True,
        prefill_blocks=True, rope_theta=1e6, mtp_modules=mtp,
        mtp_rope=False, experts=experts if routed else None, **over)


@pytest.fixture(scope="module")
def params():
    model = tfm.TransformerLM(_config())
    return model.init(jax.random.PRNGKey(1),
                      jnp.zeros((1, 4), jnp.int32))["params"]


def _without_module(tree):
    return {key: value for key, value in tree.items()
            if key != tfm.MTP_NAME}


def _agreeing(params, mix=0.0):
    """Weights under which draft and stack agree: every output
    projection zeroed (the stream stays the embedding, so the token
    after t is a function f(t) of t alone) and the module's
    projection passing the NEXT token's embedding (so the module says
    f of it too). ``mix`` > 0 adds that much of the CURRENT token's
    normed stream: some drafts then miss."""
    def zeroed(tree, path=()):
        out = {}
        for key, value in tree.items():
            if isinstance(value, dict):
                out[key] = zeroed(value, path + (key,))
            elif key in ("experts_down", "shared_down") or (
                    key == "kernel"
                    and path[-1] in ("o_proj", "down_proj")):
                out[key] = jnp.zeros_like(value)
            else:
                out[key] = value
        return out

    out = zeroed(params)
    eye = jnp.eye(D, dtype=jnp.float32)
    out[tfm.MTP_NAME]["proj"]["kernel"] = jnp.concatenate(
        [eye, mix * eye], axis=0)
    return out


def _requests(count, seed=0, low=3, high=20, new=(1, 24)):
    rng = np.random.default_rng(seed)
    return [serving.Request(
        f"r{r}", rng.integers(1, VOCAB, int(rng.integers(low, high))
                              ).tolist(),
        max_new_tokens=int(rng.integers(*new))) for r in range(count)]


def _serve(config, params, requests, each_step=None, **engine_args):
    engine_args.setdefault("num_slots", 3)
    engine_args.setdefault("kv_page_size", PAGE)
    engine = serving.ContinuousBatcher(config, params,
                                       max_decode_len=64, **engine_args)
    for request in requests:
        engine.submit(dataclasses.replace(request))
    done = {}
    while engine.pending():
        if each_step is not None:
            each_step(engine)
        for request_id, tokens in engine.step():
            done[request_id] = tokens
    return done, engine


def _both(params, requests, **engine_args):
    """(tokens with the module, its engine, tokens without, that
    engine) of the same requests."""
    with_module, drafting = _serve(_config(), params, requests,
                                   **engine_args)
    plain, engine = _serve(_config(mtp=0), _without_module(params),
                           requests, **engine_args)
    return with_module, drafting, plain, engine


# ----------------------------------------------------------- lossless


WEIGHTS = {"seeded": None, "agreeing": 0.0, "mixed": 0.6}


@pytest.mark.parametrize("weights", sorted(WEIGHTS))
@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_the_module_changes_how_many_tokens_land_never_which(
        params, weights, paged):
    """Prompts of 3 to 19 tokens and 1 to 23 new ones over pages of 4
    and a window of 8: every request crosses page boundaries and wraps
    its rings, and some end on the first token of a two-token
    landing."""
    if weights != "seeded":
        params = _agreeing(params, WEIGHTS[weights])
    requests = _requests(7, seed=3)
    got, drafting, want, plain = _both(
        params, requests, kv_page_size=PAGE if paged else None)
    assert got == want
    assert {r.request_id: len(got[r.request_id]) for r in requests} \
        == {r.request_id: r.max_new_tokens for r in requests}
    stats = drafting.step_stats()
    assert "mtp_drafted" not in plain.step_stats()
    if weights == "seeded":
        # a seeded module is right about once in a vocabulary
        assert stats["mtp_accepted"] <= stats["mtp_drafted"] // 8
        assert 0 <= plain.decode_steps - drafting.decode_steps \
            <= stats["mtp_accepted"]
    elif weights == "agreeing":
        # every draft is right: two tokens a slot a step
        assert stats["mtp_accepted"] == stats["mtp_drafted"] > 0
        assert drafting.decode_steps < 0.62 * plain.decode_steps
    else:
        assert 0 < stats["mtp_accepted"] < stats["mtp_drafted"]
        assert drafting.decode_steps < plain.decode_steps


def test_agreeing_and_disagreeing_slots_share_a_batch(params):
    """Under the mixed weights one landing hands over one token for
    some slots and two for others, each request's indices in order."""
    batches = []
    engine = serving.ContinuousBatcher(
        _config(), _agreeing(params, 0.6), num_slots=4,
        max_decode_len=64, kv_page_size=PAGE)
    engine.on_tokens = batches.append
    for request in _requests(6, seed=8, new=(12, 24)):
        engine.submit(request)
    while engine.pending():
        engine.step()
    mixed = 0
    seen = {}
    for batch in batches:
        per_request = {}
        for request_id, _token, index in batch:
            assert index == seen.get(request_id, -1) + 1
            seen[request_id] = index
            per_request[request_id] = per_request.get(request_id, 0) + 1
        assert set(per_request.values()) <= {1, 2}
        mixed += len(set(per_request.values())) == 2
    assert mixed > 0


@pytest.mark.parametrize("impl,slots", [(None, 8), ("kernel", 4)],
                         ids=["gather", "kernel"])
def test_a_drafting_engine_serves_alike_beside_parked_slots(params,
                                                            impl, slots):
    """The verify-and-draft step hands its ``active`` mask to BOTH of
    its forwards (the stack's two query positions and the module's):
    two requests among parked slots, each handed to every pool's and
    ring's two-position call at length 0 (zeros on the windowed gather
    and, in interpret mode, on the kernel), land the tokens and the
    accepted counts of an engine of just two slots."""
    from jax.experimental.pallas import tpu as pltpu

    config = _config(paged_attention_impl=impl)
    weights = _agreeing(params, 0.6)
    requests = _requests(2, seed=4, new=(6, 10))
    with (pltpu.force_tpu_interpret_mode() if impl
          else contextlib.nullcontext()):
        crowded, engine = _serve(config, weights, requests,
                                 num_slots=slots)
        alone, small = _serve(config, weights, requests, num_slots=2)
    assert crowded == alone and len(crowded) == 2
    for key in ("mtp_drafted", "mtp_accepted", "decode_steps"):
        assert engine.step_stats()[key] == small.step_stats()[key]
    assert 0 < engine.step_stats()["mtp_accepted"]


def test_a_drafting_engine_hands_first_chunks_on_past_parked_slots(
        params):
    """Five requests over four slots on the kernel (interpret mode):
    seated, finished and seated again beside parked slots, every
    pool's and ring's two-position call fetching each seated slot's
    first chunk behind the last chunk of the seated slot before it.
    The tokens are those each request lands ALONE in the same engine
    (one seated slot starts its own first chunk: the kernel as it was
    until PR 48), and occupancy() counts the hand-overs a call of the
    next step makes: the decoding slots less one."""
    from jax.experimental.pallas import tpu as pltpu

    config = _config(paged_attention_impl="kernel")
    weights = _agreeing(params, 0.6)
    requests = _requests(5, seed=9, new=(2, 14))
    counted = []

    def count(engine):
        state = engine.occupancy()
        assert state["kv_first_chunks_prefetched"] == max(
            state["slots_active"] - 1, 0)
        counted.append(state["kv_first_chunks_prefetched"])

    with pltpu.force_tpu_interpret_mode():
        together, engine = _serve(config, weights, requests,
                                  each_step=count, num_slots=4)
        for request in requests[:2]:
            alone, _ = _serve(config, weights, [request], num_slots=4)
            assert alone == {request.request_id:
                             together[request.request_id]}
    assert len(together) == 5 and max(counted) == 3
    assert len(set(counted)) >= 3       # slots freed and seated again
    assert engine.occupancy()["kv_first_chunks_prefetched"] == 0


@pytest.mark.parametrize("new_tokens", [1, 2, 3, 4, 5])
def test_max_new_tokens_cuts_a_landing_short(params, new_tokens):
    """Under agreeing weights every step could land two tokens: a
    request of an odd count past the first ends on the FIRST token of
    a landing and the second is overshoot, never served."""
    requests = [serving.Request("r0", [5, 9, 2, 44, 17], new_tokens),
                serving.Request("r1", [7, 7, 3], 9)]
    got, drafting, want, _plain = _both(_agreeing(params), requests)
    assert got == want
    assert len(got["r0"]) == new_tokens
    # first token + pairs: an even count leaves one token of a landing
    # over, and the step dispatched behind a request's last landing
    # computes two more (r1's nine tokens end on a pair)
    assert drafting.step_stats()["overshoot_tokens"] >= 2 + (
        new_tokens % 2 == 0)


def test_eos_ends_a_request_inside_a_landing(params):
    agreeing = _agreeing(params)
    free, _engine = _serve(_config(), agreeing,
                           [serving.Request("r0", [5, 9, 2], 12)])
    eos = free["r0"][4]
    request = serving.Request("r0", [5, 9, 2], 12, eos_id=eos)
    got, _d, want, _p = _both(agreeing, [request])
    assert got == want
    assert got["r0"] == free["r0"][:free["r0"].index(eos) + 1]


@pytest.mark.parametrize("weights", ["seeded", "agreeing"])
def test_a_preemption_with_two_tokens_in_flight_is_lossless(params,
                                                            weights):
    """A pool too small for its callers (overcommit): a dry pool
    lands what is in flight (a verify block's two tokens) before it
    evicts, and the victim resumes to the same tokens."""
    if weights == "agreeing":
        params = _agreeing(params)
    requests = _requests(6, seed=5, low=10, high=20, new=(16, 24))
    args = dict(kv_num_pages=14, overcommit=True, num_slots=3)
    got, drafting, want, _plain = _both(params, requests, **args)
    ample, _engine = _serve(_config(), params, requests)
    assert drafting.preemptions > 0
    assert got == want == ample


def test_the_books_reckon_the_worst_case_and_settle(params):
    """Between calls a slot's tokens in flight are 0 or 1 + drafts (a
    step unread), never more than 2 x that inside the books' reach,
    its launches 0 or 1; the pool covers every position the worst case
    could write; and nothing is in flight once the engine is idle."""
    def each_step(engine):
        engine.pages.check()
        for i, slot in enumerate(engine._slots):
            if slot.request is None:
                continue
            assert slot.launches in (0, 1)
            assert slot.in_flight in (0, 1, 2)
            if slot.decoding():
                held = len(engine.pages._slot_pages[i]) + len(
                    engine.pages._slot_shared[i])
                assert held * PAGE >= min(
                    slot.held_tokens() - 1,
                    len(slot.request.prompt)
                    + slot.request.max_new_tokens)
        state = engine.occupancy()
        assert state["live_tokens"] == sum(
            slot.held_tokens() for slot in engine._slots
            if slot.decoding())

    done, engine = _serve(_config(), _agreeing(params, 0.6),
                          _requests(8, seed=2), each_step)
    assert len(done) == 8
    assert all(slot.request is None and slot.in_flight == 0
               for slot in engine._slots)
    assert engine.occupancy()["kv_pages_in_use"] == 0


def test_the_step_order_is_the_lookaheads(params):
    """The drafting step is dispatched before its predecessor lands:
    steps_overlapped as for a model without a module, no settle but the
    idle ones."""
    requests = _requests(5, seed=4, new=(10, 20))
    _got, drafting, _want, plain = _both(params, requests)
    for engine in (drafting, plain):
        stats = engine.step_stats()
        assert stats["steps_overlapped"] >= stats["decode_steps"] - \
            sum(stats["settles"].values()) - 1
        assert stats["settles"]["preempt"] == 0
    assert drafting.step_stats()["steps_overlapped"] == \
        plain.step_stats()["steps_overlapped"]


# -------------------------------------------------- counters, records


def test_counters_rows_and_launch_records(params, tmp_path,
                                          monkeypatch):
    from batch_shipyard_tpu.trace import spans as trace_spans
    path = tmp_path / "spans.jsonl"
    monkeypatch.setenv("SHIPYARD_TRACE_FILE", str(path))
    monkeypatch.setenv("SHIPYARD_TRACE_ID", "t")
    monkeypatch.setenv("SHIPYARD_TRACE_SPAN_ID", "s")
    _done, engine = _serve(_config(), _agreeing(params, 0.6),
                           _requests(6, seed=6))
    trace_spans.flush()
    rows = [json.loads(line)["attrs"] for line in open(path)
            if json.loads(line)["kind"] == trace_spans.SPAN_SERVE_STEP]
    stats = engine.step_stats()
    decodes = [launch for row in rows for launch in row["landed"]
               if launch["kind"] == "decode"]
    assert len(decodes) == stats["launches"]["decode"]
    # one draft a seated slot a launch; a launch lands its rows and
    # what it accepted
    assert stats["mtp_drafted"] == sum(l["rows"] for l in decodes)
    assert stats["mtp_accepted"] == sum(l["accepted"] for l in decodes)
    assert all(l["tokens"] == l["rows"] + l["accepted"]
               and 0 <= l["accepted"] <= l["rows"] for l in decodes)
    assert sum(row["mtp_drafted"] for row in rows) == \
        stats["mtp_drafted"]
    assert sum(row["mtp_accepted"] for row in rows) == \
        stats["mtp_accepted"] > 0
    # the routed counters count both positions of every row, the
    # module's layer among the decision layers
    assert engine._decision_layers == ("layer_3", "layer_5", "layer_7",
                                       "mtp")
    assert engine.occupancy()["experts_held"] == 4 * 4
    assert stats["expert_pairs_chosen"] == \
        stats["mtp_drafted"] * 2 * 4 * 2


def test_a_plain_engines_launch_records_land_their_rows(params):
    _done, engine = _serve(_config(mtp=0), _without_module(params),
                           _requests(3, seed=6))
    decodes = [l.entry() for l in engine._ring if l.kind == "decode"]
    assert decodes and all(
        l["tokens"] == l["rows"] and l["accepted"] == 0
        for l in decodes)


@pytest.mark.parametrize("weights", ["seeded", "agreeing", "mixed"])
def test_take_decisions_hands_over_committed_positions_only(params,
                                                            weights):
    """One row a FED and committed position, prompt + served tokens
    less the last, for the stack's routed layers and the module's
    alike; and they are the choices a teacher-forced forward makes."""
    if weights != "seeded":
        params = _agreeing(params, WEIGHTS[weights])
    requests = _requests(4, seed=9, new=(6, 16))
    done, engine = _serve(_config(), params, requests)
    model = tfm.TransformerLM(_config())

    @jax.jit
    def chosen(tokens, following):
        """The teacher-forced forward's choices, stack then module,
        over a sequence padded at its end (everything is causal: no
        position that is read sees the padding): ONE compiled program
        for every request."""
        (_logits, hidden), sown = model.apply(
            {"params": params}, tokens, stack_hidden=True,
            mutable=["decisions"])
        _out, module = model.apply(
            {"params": params}, following, mtp_hidden=hidden,
            mutable=["decisions"])
        return jnp.concatenate([
            tfm.collect_decisions(sown["decisions"], model.config)[:, 0],
            tfm.collect_decisions(module["decisions"], model.config,
                                  mtp=True)[:, 0]])

    for request in requests:
        record = engine.take_decisions(request.request_id)
        served = done[request.request_id]
        fed = len(request.prompt) + len(served) - 1
        assert record["first"] == 0
        assert set(record["layers"]) == {"layer_3", "layer_5",
                                         "layer_7", "mtp"}
        assert {rows.shape for rows in record["layers"].values()} == {
            (fed, 2)}
        sequence = jnp.asarray(
            request.prompt + served + [0] * (40 - fed), jnp.int32)
        want = np.asarray(chosen(sequence[None, :-1],
                                 sequence[None, 1:]))[:, :fed]
        got = np.stack([record["layers"][name] for name in (
            "layer_3", "layer_5", "layer_7", "mtp")])
        # near ties aside (zeroed outputs make none; seeded weights a
        # few), the sets are the same
        same = (np.sort(got, -1) == np.sort(want, -1)).all(-1).mean()
        assert same > 0.97
        assert engine.take_decisions(request.request_id) is None


def test_a_module_without_routed_experts_drafts_too(params):
    """mtp_modules on a stack of dense feed-forward layers: the
    module's layer is attn + mlp, nothing is recorded, lossless."""
    config = _config(routed=False)
    dense_params = tfm.TransformerLM(config).init(
        jax.random.PRNGKey(2), jnp.zeros((1, 4), jnp.int32))["params"]
    assert set(dense_params["mtp"]["layer_1"]) == {"norm", "mlp"}
    requests = _requests(4, seed=1)
    got, engine = _serve(config, dense_params, requests)
    want, _plain = _serve(_config(mtp=0, routed=False),
                          _without_module(dense_params), requests)
    assert got == want
    assert engine._decision_layers == ()
    assert engine.step_stats()["mtp_drafted"] > 0


def test_two_tokens_a_handover_reach_a_stream_in_order(params):
    """Through the front end: a landing's two tokens are two lines of
    the stream, TPOT is over tokens, and the writer was fed fewer
    hand-overs than tokens."""
    from batch_shipyard_tpu.models.server import ServingFrontEnd
    engine = serving.ContinuousBatcher(
        _config(), _agreeing(params), num_slots=2, max_decode_len=64,
        kv_page_size=PAGE)
    front = ServingFrontEnd(engine, port=0).start()
    try:
        host, port = front.address
        conn = http.client.HTTPConnection(host, port, timeout=120)
        conn.request("POST", "/v1/generate", body=json.dumps(
            {"prompt": [5, 17, 31, 2], "max_new_tokens": 11,
             "stream": True}),
            headers={"Content-Type": "application/json"})
        lines = [json.loads(line) for line in
                 conn.getresponse().read().decode().strip().split("\n")]
        conn.close()
        with urllib.request.urlopen(f"{front.url}/v1/stats",
                                    timeout=30) as resp:
            stats = json.loads(resp.read())
        with urllib.request.urlopen(f"{front.url}/metrics",
                                    timeout=30) as resp:
            metrics = resp.read().decode()
    finally:
        front.shutdown()
    events = [line for line in lines if "token" in line]
    assert [e["index"] for e in events] == list(range(11))
    assert lines[-1]["tokens"] == [e["token"] for e in events]
    assert lines[-1]["num_tokens"] == 11 and lines[-1]["tpot_ms"] > 0
    want, _plain = _serve(
        _config(mtp=0), _without_module(_agreeing(params)),
        [serving.Request("r", [5, 17, 31, 2], 11)])
    assert lines[-1]["tokens"] == want["r"]
    block = stats["engine"]
    assert block["mtp_accepted"] == block["mtp_drafted"] > 0
    assert "shipyard_serving_mtp_drafted_total" in metrics
    assert "shipyard_serving_mtp_accepted_total" in metrics
    assert stats["stream_tokens_written"] == 11
    assert stats["stream_handovers"] < 11


# ------------------------------------------------------- what refuses


def test_what_is_still_refused_and_where(params):
    config = _config()
    with pytest.raises(ValueError, match="temperature == 0"):
        serving.ContinuousBatcher(
            config, params, num_slots=2, max_decode_len=64,
            sampling=inf.SamplingConfig(temperature=0.7))
    spec = serving.SpeculativeConfig(_config(mtp=0), None, gamma=2)
    with pytest.raises(ValueError, match="mtp_modules"):
        serving.ContinuousBatcher(
            _config(mtp=0), _without_module(params), num_slots=2,
            max_decode_len=64, speculative=spec)
    tied = dataclasses.replace(
        _config(windows=None), tie_embeddings=True)
    with pytest.raises(ValueError, match="drafts by itself"):
        serving.ContinuousBatcher(
            tied, params, num_slots=2, max_decode_len=64,
            speculative=serving.SpeculativeConfig(tied, None, gamma=2))
    with pytest.raises(NotImplementedError, match="one module"):
        tfm.decision_layer_names(_config(mtp=2))


def test_the_ring_holds_the_window_and_the_draft(params):
    """ring_pages counts the tokens a step may write beyond the one it
    commits: ceil((window + drafts) / page) + 1."""
    plain = serving.ContinuousBatcher(
        _config(mtp=0), _without_module(params), num_slots=2,
        max_decode_len=64, kv_page_size=PAGE)
    drafting = serving.ContinuousBatcher(
        _config(), params, num_slots=2, max_decode_len=64,
        kv_page_size=PAGE)
    assert tfm.ring_pages(plain.config, WINDOW) == 8 // 4 + 1
    assert tfm.ring_pages(drafting.config, WINDOW) == 9 // 4 + 1 + 1
    assert drafting.config.spec_window == 1
    ring = drafting.cache["layer_0"]["attn"]["k_ring"]
    assert ring.shape[0] == 2 * 4
    # the module's K/V is a subtree of the same cache, behind a block
    # table into the same pool
    leaves = drafting.cache["mtp"]["layer_0"]["attn"]
    assert set(leaves) == {"k_pages", "v_pages", "block_table",
                           "length"}
    assert tfm.paged_layer_count(drafting.config) == 2
    assert tfm.attention_windows(drafting.config) == (
        WINDOW, WINDOW, 0, WINDOW, 0)


def test_the_serve_report_names_the_drafter():
    from batch_shipyard_tpu.workloads import serve
    config = dataclasses.replace(_config(), kv_page_size=PAGE,
                                 kv_num_pages=9)
    assert serve.paged_decode_impl(config) == "xla_windowed"
    assert pa.paged_decode_road(None, grouped=True, positions=2) == \
        "xla_windowed"
    assert pa.paged_decode_road("kernel", grouped=False,
                                positions=2) == "gqa_kernel"
    assert pa.paged_decode_road(None, grouped=True) == "xla"
    with pytest.raises(NotImplementedError):
        pa.paged_decode_road(None, grouped=False, int8=True,
                             positions=2)


# ------------------------------ the kernel at several query positions


def _pool(rng, batch, entries, page, kv_heads, depth):
    pages = batch * entries + 1
    k = jnp.asarray(rng.normal(size=(pages, page, kv_heads * depth)),
                    jnp.float32)
    v = jnp.asarray(rng.normal(size=(pages, page, kv_heads * depth)),
                    jnp.float32)
    table = jnp.asarray(np.arange(batch * entries).reshape(
        batch, entries), jnp.int32)
    return k, v, table


def _dense_view(pool, table, length, page, kv_heads, depth):
    """The slot's keys by position, through the ring rule (entry c
    holds the newest logical page p <= the last with p % T == c)."""
    entries = table.shape[0]
    out = np.zeros((length, kv_heads, depth), np.float32)
    last = (length - 1) // page
    for entry in range(entries):
        logical = last - ((last - entry) % entries)
        for row in range(page):
            position = logical * page + row
            if logical >= 0 and position < length:
                out[position] = np.asarray(
                    pool[table[entry], row]).reshape(kv_heads, depth)
    return out


@pytest.mark.parametrize("positions, window, entries", [
    (1, 0, 8), (2, 0, 8), (2, 16, 8), (2, 16, 4), (1, 16, 3),
    (3, 16, 5), (2, 8, 3)])
def test_the_grouped_kernel_at_several_query_positions(positions,
                                                       window, entries):
    """Against mha_reference on each slot's keys laid out by position:
    query r of S sits at key position length - S + r, sees the keys up
    to its own and, in a window layer, its newest ``window``; through
    a block table (entries as wide as the context) and through a ring
    (narrower); the Pallas kernel in interpret mode and the XLA gather
    alike."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.default_rng(positions * 100 + window + entries)
    batch, heads, kv_heads, depth, page = 3, 8, 2, 16, 8
    if window:
        assert entries >= -(-(window + positions - 1) // page) + 1
    lengths = np.array([37, 9, 2 + positions], np.int32)
    lengths = np.minimum(lengths, entries * page) if not window \
        else lengths
    k, v, table = _pool(rng, batch, entries, page, kv_heads, depth)
    q = jnp.asarray(rng.normal(size=(batch, positions, heads, depth)),
                    jnp.float32)
    gathered = pa.paged_decode_attention_xla_windowed(
        q, k, v, table, jnp.asarray(lengths), window=window)
    with pltpu.force_tpu_interpret_mode():
        kernel = pa.gqa_paged_decode_attention_kernel(
            q, k, v, table, jnp.asarray(lengths), window=window)
    np.testing.assert_allclose(kernel, gathered, atol=2e-5, rtol=2e-5)
    for b in range(batch):
        length = int(lengths[b])
        keys = _dense_view(k, np.asarray(table[b]), length, page,
                           kv_heads, depth)
        values = _dense_view(v, np.asarray(table[b]), length, page,
                             kv_heads, depth)
        for r in range(positions):
            at = length - positions + r
            low = max(at + 1 - window, 0) if window else 0
            want = attn_ops.mha_reference(
                q[b:b + 1, r:r + 1], jnp.asarray(keys[None, low:at + 1]),
                jnp.asarray(values[None, low:at + 1]), causal=False)
            np.testing.assert_allclose(gathered[b, r], want[0, 0],
                                       atol=2e-5, rtol=2e-5)


def test_one_query_position_is_the_kernel_it_was():
    """positions == 1 hands the kernel no new argument: the four
    grouped cells' program is the one it was (tests/test_tpu_lowering
    holds their lowerings to recorded digests)."""
    seen = {}
    real = pa.pl.pallas_call

    def spy(kernel, **kwargs):
        seen[kernel.keywords["window"]] = kernel.keywords
        return real(kernel, **kwargs)

    rng = np.random.default_rng(0)
    k, v, table = _pool(rng, 2, 4, 8, 2, 16)
    lengths = jnp.asarray([9, 20], jnp.int32)
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        pa.pl.pallas_call = spy
        try:
            for positions in (1, 2):
                q = jnp.ones((2, positions, 8, 16), jnp.float32)
                # (a fresh window each time: the kernel is jitted)
                pa.gqa_paged_decode_attention_kernel(
                    q, k, v, table, lengths, window=12 + positions)
        finally:
            pa.pl.pallas_call = real
    assert "positions" not in seen[13]
    assert seen[14]["positions"] == 2


# ------------------------- the dense layer kind and the q/k norms


def test_the_mlp_kind_is_one_gated_feed_forward_after_one_norm(params):
    config = _config(mtp=0)
    assert "mlp" in tfm.MIXER_KINDS and "mlp" not in tfm.STATEFUL_KINDS
    assert set(params["layer_1"]) == {"norm", "mlp"}
    assert set(params["layer_1"]["mlp"]) == {"gate_proj", "up_proj",
                                             "down_proj"}
    assert params["layer_1"]["mlp"]["gate_proj"]["kernel"].shape == (
        D, 48)
    assert tfm.decision_layer_names(config) == ("layer_3", "layer_5",
                                                "layer_7")
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 5, D))
    block = tfm.MixerBlock(config, "mlp")
    out, normed = block.apply({"params": params["layer_1"]}, x, None)
    w = params["layer_1"]["mlp"]
    want = x + (jax.nn.silu(normed @ w["gate_proj"]["kernel"])
                * (normed @ w["up_proj"]["kernel"])
                ) @ w["down_proj"]["kernel"]
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)


def test_qk_norms_are_one_scale_of_d_head_a_layer_each(params):
    attn = params["layer_0"]["attn"]
    assert attn["q_norm"]["scale"].shape == (8,)
    assert attn["k_norm"]["scale"].shape == (8,)
    plain = tfm.TransformerLM(dataclasses.replace(
        _config(mtp=0), qk_norm=False)).init(
            jax.random.PRNGKey(1), jnp.zeros((1, 4), jnp.int32))
    assert "q_norm" not in plain["params"]["layer_0"]["attn"]
    # q and k are normed a head before the rotation: scaling a
    # projection by a constant changes nothing
    model = tfm.TransformerLM(_config(mtp=0))
    tokens = jnp.asarray([[3, 9, 27, 14, 5, 8]], jnp.int32)
    stack = _without_module(params)
    base = model.apply({"params": stack}, tokens)
    scaled = jax.tree_util.tree_map(lambda x: x, stack)
    scaled["layer_2"]["attn"]["q_proj"]["kernel"] = \
        4.0 * stack["layer_2"]["attn"]["q_proj"]["kernel"]
    np.testing.assert_allclose(
        model.apply({"params": scaled}, tokens), base, atol=2e-4,
        rtol=2e-4)


def test_a_forward_that_does_not_ask_is_what_it_is_without_a_module(
        params):
    tokens = jnp.asarray([[3, 9, 27, 14, 5, 8]], jnp.int32)
    with_module = tfm.TransformerLM(_config()).apply(
        {"params": params}, tokens)
    without = tfm.TransformerLM(_config(mtp=0)).apply(
        {"params": _without_module(params)}, tokens)
    assert (np.asarray(with_module) == np.asarray(without)).all()
    with pytest.raises(ValueError, match="no multi-token"):
        tfm.TransformerLM(_config(mtp=0)).apply(
            {"params": _without_module(params)}, tokens,
            mtp_hidden=jnp.zeros((1, 6, D)))

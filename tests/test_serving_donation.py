"""The serving step programs consume the cache they are given
(models/serving.py donates it to _decode_step, the prefills and
_speculative_step): the pool is updated in place, the engine never
holds the cache of before a step, and a front end whose engine lost
its cache in a failed step leaves rotation instead of failing every
step for ever."""

import dataclasses
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.compilecache import aot
from batch_shipyard_tpu.models import inference as inf
from batch_shipyard_tpu.models import serving
from batch_shipyard_tpu.models import transformer as tfm
from batch_shipyard_tpu.models.server import ServingFrontEnd

CFG = tfm.TransformerConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=2, d_head=16,
    d_ff=64, max_seq_len=64, dtype=jnp.float32,
    param_dtype=jnp.float32)
PAGE = 8
# kind -> (kv_cache_dtype, ContinuousBatcher keywords)
KINDS = {
    "dense": (None, {}),
    "paged": (None, {"kv_page_size": PAGE}),
    "paged-int8": ("int8", {"kv_page_size": PAGE}),
    "speculative": (None, {"kv_page_size": PAGE, "speculative": True}),
}


@pytest.fixture(scope="module")
def params():
    return tfm.TransformerLM(CFG).init(
        jax.random.PRNGKey(7), jnp.zeros((1, 8), jnp.int32))["params"]


def _engine(kind, params):
    kv_dtype, kwargs = KINDS[kind]
    kwargs = dict(kwargs)
    if kwargs.pop("speculative", False):
        # The target as its own draft: every draft is accepted, so the
        # blocks cross pages several tokens at a time and the outputs
        # are the lockstep decoder's exactly.
        kwargs["speculative"] = serving.SpeculativeConfig(
            CFG, params, gamma=3)
    cfg = dataclasses.replace(CFG, kv_cache_dtype=kv_dtype)
    return cfg, serving.ContinuousBatcher(
        cfg, params, num_slots=2, max_decode_len=64, **kwargs)


def _caches(engine):
    caches = [engine.cache]
    if engine.speculative is not None:
        caches.append(engine._draft_cache)
    return caches


def _held(engine):
    """Every leaf the engine's caches hold but the block tables: a
    table pushed from the host between two steps replaces the leaf,
    and the replaced one is dropped, not consumed."""
    return [leaf for path, leaf
            in jax.tree_util.tree_leaves_with_path(_caches(engine))
            if path[-1].key != "block_table"]


def _aliased_bytes(lowered):
    return lowered.compile().memory_analysis().alias_size_in_bytes


def _tree_bytes(tree):
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("kind", list(KINDS))
def test_step_programs_consume_the_cache(kind, params):
    cfg, engine = _engine(kind, params)
    rng = np.random.RandomState(5)
    # 5..7 prompt tokens and 14 new ones on pages of 8: every request
    # crosses a page boundary twice while it decodes, on a model of
    # two layers (one table buffer under both layers' leaves would be
    # donated twice).
    requests = [serving.Request(
        f"r{i}", [int(t) for t in rng.randint(1, 97, (n,))],
        max_new_tokens=14) for i, n in enumerate((5, 6, 7))]
    for request in requests:
        engine.submit(request)
    done, steps = {}, 0
    while engine.pending():
        held = _held(engine)
        before = dict(engine.step_stats()["phase_seconds"])
        for request_id, tokens in engine.step():
            done[request_id] = tokens
        steps += 1
        assert steps < 200
        # Every step of a loaded engine prefills or decodes: what it
        # was given is gone, what it holds now is alive. The one
        # exception runs no program: a call that only reads back the
        # last tokens of the step in flight.
        after = engine.step_stats()["phase_seconds"]
        ran = any(after[phase] > before[phase]
                  for phase in ("prefill", "dispatch"))
        assert all(leaf.is_deleted() == ran for leaf in held), steps
        assert not engine.cache_lost()
    # The same tokens as the lockstep decoder (models/inference has a
    # loop and a cache of its own, and donates nothing).
    run, _model = inf.make_decoder(cfg, params, max_decode_len=64)
    for request in requests:
        want, _cache = run(jnp.asarray([request.prompt], jnp.int32),
                           request.max_new_tokens,
                           jax.random.PRNGKey(0))
        assert done[request.request_id] == list(np.asarray(
            want[0, len(request.prompt):])), request.request_id

    # The compiled programs alias every leaf of the caches they are
    # given, input to output: nothing of a pool is copied or held
    # twice.
    abstract = aot.abstractify
    state = abstract((engine._tokens, engine._positions,
                      engine._active))
    cache, p_abs = abstract(engine.cache), abstract(engine.params)
    prompt = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    dense_model = engine._prefill.args[0]
    if engine.speculative is not None:
        step = serving._speculative_step.lower(
            engine.model, engine._spec_step.args[1], engine.gamma,
            p_abs, abstract(engine._draft_params), cache,
            abstract(engine._draft_cache), *state)
        assert _aliased_bytes(step) == _tree_bytes(_caches(engine))
    else:
        step = serving._decode_step.lower(
            engine.model, engine.sampling, p_abs, cache, *state,
            abstract(engine._key))
        assert _aliased_bytes(step) == _tree_bytes(engine.cache)
    if engine.paged:
        row = jax.ShapeDtypeStruct((engine.max_blocks,), jnp.int32)
        prefill = serving._prefill_paged.lower(
            dense_model, None, PAGE, p_abs, cache, 0, prompt, row, 16)
        ids = jax.ShapeDtypeStruct((64 // PAGE,), jnp.int32)
        shared = serving._prefill_paged_shared.lower(
            dense_model, None, PAGE, p_abs, cache, 0, prompt, ids,
            row, row, PAGE, 16)
        assert _aliased_bytes(shared) == _tree_bytes(engine.cache)
    else:
        prefill = serving._prefill_dense.lower(
            dense_model, None, p_abs, cache, 0, prompt, 16)
    assert _aliased_bytes(prefill) == _tree_bytes(engine.cache)


def _get(url, path):
    try:
        with urllib.request.urlopen(f"{url}{path}", timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read())


def test_a_step_that_loses_the_cache_takes_the_replica_out_of_rotation(
        params, caplog):
    """A step made to raise AFTER its cache was consumed: the
    in-flight requests end with an error the router resumes from, and
    /healthz turns 503 — once; the engine thread does not go on
    failing the same step."""
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=1, max_decode_len=64, kv_page_size=PAGE)
    sound, calls = engine._decode_step, []

    def consumed_then_raised(*args):
        calls.append(1)
        sound(*args)            # the donated cache is gone with this
        raise RuntimeError("injected: the step failed on the device")

    front = ServingFrontEnd(engine, port=0).start()
    try:
        assert _get(front.url, "/healthz") == (200, {"ok": True})
        engine._decode_step = consumed_then_raised
        replies = {}

        def post(name):
            request = urllib.request.Request(
                f"{front.url}/v1/generate", data=json.dumps({
                    "request_id": name, "prompt": [3, 7, 11],
                    "max_new_tokens": 8}).encode(),
                headers={"Content-Type": "application/json"},
                method="POST")
            try:
                with urllib.request.urlopen(request,
                                            timeout=60) as resp:
                    replies[name] = (resp.status,
                                     json.loads(resp.read()))
            except urllib.error.HTTPError as exc:
                replies[name] = (exc.code, json.loads(exc.read()))

        clients = [threading.Thread(target=post, args=(name,),
                                    daemon=True)
                   for name in ("active", "queued")]
        for client in clients:
            client.start()
        for client in clients:
            client.join(timeout=60)
            assert not client.is_alive()
        # One slot: one request was decoding, the other waited in
        # line. Both end with the draining marker and the cause.
        for name in ("active", "queued"):
            status, body = replies[name]
            assert status == 503 and body["draining"] is True, body
        assert any("KV cache lost in a failed step" in body["error"]
                   for _status, body in replies.values())
        assert engine.cache_lost() and front.draining
        assert _get(front.url, "/healthz") == (
            503, {"ok": False, "draining": True})
        # Once: the loop parks, no step runs against the dead cache.
        time.sleep(0.5)
        assert len(calls) == 1
        assert sum("engine step failed" in record.getMessage()
                   for record in caplog.records) == 1
        assert not engine.pending()
        # ... and nothing new is seated on it.
        post("late")
        assert replies["late"][0] == 503
        assert replies["late"][1]["draining"] is True
    finally:
        front.shutdown()


def test_a_step_that_fails_with_its_cache_intact_is_only_logged(params):
    """The failure path asks the cache, not the exception: a step
    that raises before anything was consumed leaves the replica in
    rotation, as before."""
    engine = serving.ContinuousBatcher(
        CFG, params, num_slots=1, max_decode_len=64, kv_page_size=PAGE)
    sound, failures = engine._decode_step, []

    def raises_once(*args):
        if not failures:
            failures.append(1)
            raise RuntimeError("injected: failed before dispatch")
        return sound(*args)

    engine._decode_step = raises_once
    front = ServingFrontEnd(engine, port=0).start()
    try:
        result = front.generate({"prompt": [3, 7, 11],
                                 "max_new_tokens": 4})
        assert failures and len(result["tokens"]) == 4
        assert not front.draining and not engine.cache_lost()
        assert _get(front.url, "/healthz") == (200, {"ok": True})
    finally:
        front.shutdown()

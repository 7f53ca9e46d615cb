"""int8 KV cache (kv_cache_dtype='int8'): half the HBM per cached
token. Accuracy vs the fp cache (logits within quantization noise),
storage dtype actually int8, generation + continuous-batching engine
end-to-end, and the paged-combination guard."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.models import inference as inf
from batch_shipyard_tpu.models import serving
from batch_shipyard_tpu.models import transformer as tfm

CFG = tfm.TransformerConfig(
    vocab_size=97, d_model=64, n_layers=2, n_heads=4, d_head=16,
    d_ff=128, max_seq_len=64, dtype=jnp.float32,
    param_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return tfm.TransformerLM(CFG).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]


def _decode_model(kv_dtype):
    cfg = dataclasses.replace(
        inf.decode_config(CFG, 64), kv_cache_dtype=kv_dtype)
    return tfm.TransformerLM(cfg)


def test_cache_leaves_are_int8_with_scales(params):
    model = _decode_model("int8")
    cache = inf.empty_cache(model, batch_size=2)
    leaves = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(cache):
        leaves[path[-1].key] = leaf
    assert leaves["k"].dtype == jnp.int8
    assert leaves["v"].dtype == jnp.int8
    assert leaves["k_scale"].dtype == jnp.float32
    assert leaves["v_scale"].dtype == jnp.float32
    # Capacity claim measured on the ACTUAL arrays: int8 K + its
    # scales must be under half of what the fp cache stores.
    fp_model = _decode_model(None)
    fp_cache = inf.empty_cache(fp_model, batch_size=2)
    fp_k = [leaf for path, leaf in
            jax.tree_util.tree_leaves_with_path(fp_cache)
            if path[-1].key == "k"]
    int8_bytes = leaves["k"].nbytes + leaves["k_scale"].nbytes
    assert int8_bytes <= fp_k[0].nbytes // 2


def test_int8_logits_within_quantization_noise(params):
    """Single-step decode logits with the int8 cache stay within
    ~2% relative of the fp cache's."""
    prompt = jnp.asarray([[5, 17, 31, 2, 9, 40]], jnp.int32)

    def last_logits(kv_dtype):
        model = _decode_model(kv_dtype)
        cache = inf.empty_cache(model, 1)
        hidden, _ = model.apply(
            {"params": params, "cache": cache}, prompt,
            return_hidden=True, mutable=["cache"])
        emb = params["embed"]["embedding"]
        return jnp.dot(hidden[:, -1].astype(jnp.float32),
                       emb.astype(jnp.float32).T)

    ref = last_logits(None)
    got = last_logits("int8")
    rel = (np.linalg.norm(np.asarray(got - ref)) /
           np.linalg.norm(np.asarray(ref)))
    assert rel < 0.02, rel


def test_int8_generation_runs_and_mostly_agrees(params):
    """Full 24-token greedy generation with the int8 cache: tokens
    stay in-vocab and agree with the fp run for a long prefix (the
    divergence point, if any, is an argmax near-tie under
    quantization noise)."""
    prompt = jnp.asarray([[5, 17, 31, 2], [9, 9, 1, 42]], jnp.int32)

    def run(kv_dtype):
        model = _decode_model(kv_dtype)
        cache = inf.empty_cache(model, prompt.shape[0])
        tokens, _ = inf.generate(model, params, cache, prompt, 24,
                                 jax.random.PRNGKey(0))
        return np.asarray(tokens)

    ref, got = run(None), run("int8")
    assert got.shape == ref.shape
    assert (got >= 0).all() and (got < CFG.vocab_size).all()
    agree = int((got == ref).all(axis=0).sum())
    assert agree >= ref.shape[1] // 2, (agree, ref.shape[1])


def test_int8_serving_engine_end_to_end(params):
    """ContinuousBatcher on the int8 cache: requests complete with
    in-vocab tokens through admit/decode/finish."""
    cfg = dataclasses.replace(CFG, kv_cache_dtype="int8")
    engine = serving.ContinuousBatcher(cfg, params, num_slots=2,
                                       max_decode_len=64)
    for i in range(3):
        engine.submit(serving.Request(f"r{i}", [3 + i, 7, 11],
                                      max_new_tokens=6))
    done = {}
    while engine.pending():
        for rid, tokens in engine.step():
            done[rid] = tokens
    assert set(done) == {"r0", "r1", "r2"}
    assert all(len(t) == 6 for t in done.values())
    assert all(0 <= tok < CFG.vocab_size
               for t in done.values() for tok in t)


def test_int8_paged_pool_leaves_and_engine(params):
    """int8 PAGED pool: pages stored int8 with per-row scale pages;
    the continuous batcher (incl. overcommit preemption machinery)
    runs end-to-end on it."""
    cfg = dataclasses.replace(CFG, kv_cache_dtype="int8")
    engine = serving.ContinuousBatcher(
        cfg, params, num_slots=2, max_decode_len=64,
        kv_page_size=16, overcommit=True)
    leaves = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            engine.cache):
        leaves[path[-1].key] = leaf
    assert leaves["k_pages"].dtype == jnp.int8
    assert leaves["v_pages"].dtype == jnp.int8
    assert leaves["k_page_scales"].dtype == jnp.float32
    # The pool folds the heads into its rows ([P, page, H*D], as the
    # decode kernel blocks it); the scales keep one per head.
    pages, page, width = leaves["k_pages"].shape
    assert width == CFG.n_heads * CFG.d_head
    assert leaves["k_page_scales"].shape == (pages, page, CFG.n_heads)
    for i in range(3):
        engine.submit(serving.Request(f"p{i}", [3 + i, 7, 11],
                                      max_new_tokens=6))
    done = {}
    while engine.pending():
        for rid, tokens in engine.step():
            done[rid] = tokens
    assert set(done) == {"p0", "p1", "p2"}
    assert all(len(t) == 6 for t in done.values())
    assert all(0 <= tok < CFG.vocab_size
               for t in done.values() for tok in t)


def test_int8_paged_tokens_close_to_fp_paged(params):
    """Same prompts through fp and int8 paged engines: outputs agree
    for a long prefix (divergence only at argmax near-ties under
    quantization noise)."""
    def run(kv_dtype):
        cfg = dataclasses.replace(CFG, kv_cache_dtype=kv_dtype)
        engine = serving.ContinuousBatcher(
            cfg, params, num_slots=2, max_decode_len=64,
            kv_page_size=16)
        engine.submit(serving.Request("r", [5, 17, 31, 2],
                                      max_new_tokens=16))
        out = None
        while engine.pending():
            for _rid, tokens in engine.step():
                out = tokens
        return out

    ref, got = run(None), run("int8")
    agree = 0
    for a, b in zip(ref, got):
        if a != b:
            break
        agree += 1
    assert agree >= len(ref) // 2, (agree, ref, got)


def test_unknown_kv_cache_dtype_rejected(params):
    cfg = dataclasses.replace(inf.decode_config(CFG, 64),
                              kv_cache_dtype="fp8")
    model = tfm.TransformerLM(cfg)
    with pytest.raises(ValueError):
        inf.empty_cache(model, 1)


def test_int8_kv_dequant_fusion_check_runs():
    """tools/tpu_checks.check_int8_kv_dequant_fusion (ADVICE r5): the
    check must compile the dense int8 decode step and return a
    verdict on every backend. The PASS threshold is a silicon
    question (CPU XLA is known to materialize the dequantized cache);
    here we pin that the measurement itself works and the threshold
    is the documented one-dequantized-cache footprint."""
    import pathlib
    import sys
    repo_root = str(pathlib.Path(__file__).resolve().parent.parent)
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    from tools.tpu_checks import check_int8_kv_dequant_fusion
    assert isinstance(check_int8_kv_dequant_fusion(), bool)

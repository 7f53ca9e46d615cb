"""KV-cache decode correctness: cached single-step decoding must
reproduce the full-forward teacher-forced argmax path exactly."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.models import inference, transformer as tfm


@pytest.fixture(scope="module")
def setup():
    config = tfm.TransformerConfig(
        vocab_size=97, d_model=64, n_layers=2, n_heads=4, d_head=16,
        d_ff=128, max_seq_len=64, dtype=jnp.float32,
        param_dtype=jnp.float32)
    model = tfm.TransformerLM(config)
    tokens = jnp.zeros((2, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    return config, model, params


def test_greedy_decode_matches_full_forward(setup):
    config, model, params = setup
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(0, 97, (2, 6)), jnp.int32)
    run, _ = inference.make_decoder(config, params, max_decode_len=32)
    out, _cache = run(prompt, 10, jax.random.PRNGKey(1))
    assert out.shape == (2, 16)
    np.testing.assert_array_equal(np.asarray(out[:, :6]),
                                  np.asarray(prompt))
    # Reference: ONE full forward (no cache) over what was decoded.
    # Attention is causal, so its logits at position i are those of a
    # forward over the first i + 1 tokens alone: a greedy rollout by
    # repeated full forwards picks token i + 1 from them, ten times.
    logits = jax.jit(lambda seq: model.apply({"params": params}, seq))(
        out[:, :-1])
    greedy = jnp.argmax(logits[:, 5:].astype(jnp.float32),
                        axis=-1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(out[:, 6:]),
                                  np.asarray(greedy))


def test_sampling_temperature_and_topk(setup):
    config, model, params = setup
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    run, _ = inference.make_decoder(config, params, max_decode_len=32)
    sampling = inference.SamplingConfig(temperature=1.0, top_k=5)
    out_a, _ = run(prompt, 8, jax.random.PRNGKey(7),
                   sampling=sampling)
    out_b, _ = run(prompt, 8, jax.random.PRNGKey(8),
                   sampling=sampling)
    assert out_a.shape == (1, 11)
    # Different keys should (overwhelmingly) give different samples.
    assert not np.array_equal(np.asarray(out_a), np.asarray(out_b))
    # Same key reproduces exactly.
    out_c, _ = run(prompt, 8, jax.random.PRNGKey(7),
                   sampling=sampling)
    np.testing.assert_array_equal(np.asarray(out_a),
                                  np.asarray(out_c))


def test_decode_respects_max_len(setup):
    config, model, params = setup
    run, dmodel = inference.make_decoder(config, params,
                                         max_decode_len=8)
    prompt = jnp.asarray([[5, 6]], jnp.int32)
    out, cache = run(prompt, 6, jax.random.PRNGKey(0))
    assert out.shape == (1, 8)
    # Cache index advanced exactly prompt+generated-1 writes... every
    # step writes once: prompt (2) + decode steps (5) = 7? The last
    # sampled token is never fed back. index == total forward calls.
    leaf = jax.tree_util.tree_leaves(
        {k: v for k, v in cache.items()})[0]
    assert leaf is not None


def test_multi_token_insert_matches_sequential(setup):
    """The batched prefill path (multi-token _decode_attend insert)
    must produce the same cache state and outputs as feeding the same
    tokens one step at a time — including a chunk inserted at a
    nonzero per-slot depth."""
    config, model, params = setup
    dconfig = inference.decode_config(config, max_decode_len=32)
    dmodel = tfm.TransformerLM(dconfig)
    rng = np.random.RandomState(1)
    tokens = jnp.asarray(rng.randint(0, 97, (2, 7)), jnp.int32)

    # Sequential: one token per apply.
    cache_seq = inference.empty_cache(dmodel, 2)
    outs = []
    for t in range(tokens.shape[1]):
        logits, mut = dmodel.apply(
            {"params": params, "cache": cache_seq},
            tokens[:, t:t + 1], positions=jnp.int32(t)[None],
            mutable=["cache"])
        cache_seq = mut["cache"]
        outs.append(logits[:, 0])
    seq_logits = jnp.stack(outs, axis=1)        # [B, T, vocab]

    # Batched: one multi-token apply (positions default to arange).
    cache_bat = inference.empty_cache(dmodel, 2)
    bat_logits, mut = dmodel.apply(
        {"params": params, "cache": cache_bat}, tokens,
        mutable=["cache"])
    cache_bat = mut["cache"]
    np.testing.assert_allclose(
        np.asarray(bat_logits), np.asarray(seq_logits),
        rtol=2e-5, atol=2e-5)
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(cache_seq),
            jax.tree_util.tree_leaves_with_path(cache_bat)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5,
            err_msg=str(pa))

    # Chunked continuation from depth 7: next 3 tokens in one chunk
    # vs one-at-a-time, on top of identical caches.
    more = jnp.asarray(rng.randint(0, 97, (2, 3)), jnp.int32)
    cache_a, cache_b = cache_seq, cache_bat
    for t in range(3):
        logits, mut = dmodel.apply(
            {"params": params, "cache": cache_a},
            more[:, t:t + 1], positions=jnp.int32(7 + t)[None],
            mutable=["cache"])
        cache_a = mut["cache"]
    last_seq = logits[:, 0]
    chunk_logits, mut = dmodel.apply(
        {"params": params, "cache": cache_b}, more,
        positions=jnp.arange(7, 10, dtype=jnp.int32),
        mutable=["cache"])
    np.testing.assert_allclose(
        np.asarray(chunk_logits[:, -1]), np.asarray(last_seq),
        rtol=2e-5, atol=2e-5)

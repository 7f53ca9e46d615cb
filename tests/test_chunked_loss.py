"""Chunked cross-entropy tests: Pallas kernel (interpret mode) and
scan-chunked XLA path vs the dense oracle — forward and gradients —
plus the lm_loss_chunked delegation and impl='auto' resolution."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.ops import chunked_loss as cl
from batch_shipyard_tpu.ops import ring_attention


def _dense_loss(h, e, t, ignore_id=-1):
    d = h.shape[-1]
    logits = (h.reshape(-1, d).astype(jnp.float32)
              @ e.astype(jnp.float32).T)
    tg = t.reshape(-1)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, tg[:, None].clip(0), axis=-1)[:, 0]
    mask = (tg != ignore_id)
    return jnp.sum((lse - gold) * mask) / jnp.maximum(
        jnp.sum(mask), 1)


def _rand(b, t, d, v, seed=0):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(b, t, d), jnp.float32)
    e = jnp.asarray(rng.randn(v, d) / np.sqrt(d), jnp.float32)
    tg = jnp.asarray(rng.randint(0, v, (b, t)), jnp.int32)
    return h, e, tg


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize(
    # Ragged rows (b*t % 128 != 0) and ragged vocab (v % v_chunk != 0)
    # exercise the padding + in-kernel tail-mask paths.
    "b,t,d,v", [(2, 128, 128, 1024), (2, 96, 128, 700),
                (1, 64, 256, 512)])
def test_loss_matches_dense_oracle(impl, b, t, d, v):
    h, e, tg = _rand(b, t, d, v)
    tg = tg.at[0, :5].set(-1)  # exercise the ignore mask
    got = jax.jit(lambda h, e: cl.chunked_softmax_xent(
        h, e, tg, impl=impl))(h, e)
    want = _dense_loss(h, e, tg)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_grads_match_dense_oracle(impl):
    h, e, tg = _rand(2, 96, 128, 700, seed=3)
    tg = tg.at[1, -9:].set(-1)

    def loss(h, e):
        return cl.chunked_softmax_xent(h, e, tg, impl=impl)

    gh, ge = jax.grad(loss, argnums=(0, 1))(h, e)
    rh, re = jax.grad(lambda h, e: _dense_loss(h, e, tg),
                      argnums=(0, 1))(h, e)
    for a, b_ in ((gh, rh), (ge, re)):
        rel = (np.linalg.norm(np.asarray(a - b_))
               / max(np.linalg.norm(np.asarray(b_)), 1e-30))
        assert rel < 1e-5


def test_all_tokens_ignored_is_finite():
    h, e, tg = _rand(1, 128, 128, 512, seed=5)
    tg = jnp.full_like(tg, -1)
    for impl in ("xla", "interpret"):
        got = cl.chunked_softmax_xent(h, e, tg, impl=impl)
        assert float(got) == 0.0
        gh = jax.grad(lambda h: cl.chunked_softmax_xent(
            h, e, tg, impl=impl))(h)
        assert np.all(np.isfinite(np.asarray(gh)))
        assert float(jnp.sum(jnp.abs(gh))) == 0.0


def test_lm_loss_chunked_delegates_and_matches():
    from batch_shipyard_tpu.models import transformer as tfm
    h, e, tg = _rand(2, 64, 128, 512, seed=7)
    got = tfm.lm_loss_chunked(h, e, tg)
    want = _dense_loss(h, e, tg)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_lane_misaligned_dim_falls_back_to_xla():
    # d % 128 != 0 must silently take the XLA path, not crash.
    h, e, tg = _rand(1, 64, 96, 300, seed=9)
    got = cl.chunked_softmax_xent(h, e, tg, impl="pallas")
    want = _dense_loss(h, e, tg)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


# -- impl='auto' resolution: backend and shape only -----------------

def test_auto_resolves_from_backend_and_shape(monkeypatch):
    assert cl.resolve_xent_impl("auto", 128) == "xla"
    assert ring_attention.resolve_ring_impl("auto", 1024) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert cl.resolve_xent_impl("auto", 128) == "pallas"
    # Lane-misaligned model dim: the kernel's blocks cannot tile it.
    assert cl.resolve_xent_impl("auto", 96) == "xla"
    assert ring_attention.resolve_ring_impl("auto", 1024) == "flash"
    # A shard length the flash blocks cannot tile stays on xla.
    assert ring_attention.resolve_ring_impl("auto", 100) == "xla"


def test_explicit_impls_pass_through_and_unknown_fails():
    assert ring_attention.resolve_ring_impl("pallas_dma") == \
        "pallas_dma"
    assert ring_attention.resolve_ring_impl("xla") == "xla"
    with pytest.raises(ValueError):
        ring_attention.resolve_ring_impl("bogus")
    with pytest.raises(ValueError):
        cl.resolve_xent_impl("bogus", 128)


"""Paged-attention Pallas kernel tests (interpret mode): the kernel
must agree with the XLA gather formulation for random block tables and
ragged lengths, and the transformer's paged decode path must produce
identical tokens under either implementation."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

from batch_shipyard_tpu.ops import paged_attention as pa


@pytest.fixture()
def interpret_mode():
    with pltpu.force_tpu_interpret_mode():
        yield


def _folded(pool):
    """[P, page, H, D] -> the pool's stored layout [P, page, H*D]."""
    return pool.reshape(*pool.shape[:2], -1)


def _random_case(rng, dtype, batch=4, heads=4, depth=64, page=8,
                 max_blocks=6, num_pages=32):
    q = jnp.asarray(rng.randn(batch, 1, heads, depth), dtype)
    k_pages = _folded(jnp.asarray(
        rng.randn(num_pages, page, heads, depth), dtype))
    v_pages = _folded(jnp.asarray(
        rng.randn(num_pages, page, heads, depth), dtype))
    # Distinct physical pages per slot (the allocator's invariant).
    table = jnp.asarray(
        rng.permutation(num_pages)[:batch * max_blocks].reshape(
            batch, max_blocks), jnp.int32)
    return q, k_pages, v_pages, table


def test_kernel_matches_xla_fp32(interpret_mode):
    rng = np.random.RandomState(0)
    q, k_pages, v_pages, table = _random_case(rng, jnp.float32)
    lengths = jnp.asarray([1, 5, 23, 48], jnp.int32)
    ref = pa.paged_decode_attention_xla(q, k_pages, v_pages, table,
                                        lengths)
    got = pa.paged_decode_attention_kernel(q, k_pages, v_pages, table,
                                           lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_kernel_matches_xla_bf16(interpret_mode):
    rng = np.random.RandomState(1)
    q, k_pages, v_pages, table = _random_case(rng, jnp.bfloat16)
    lengths = jnp.asarray([3, 8, 17, 41], jnp.int32)
    ref = pa.paged_decode_attention_xla(q, k_pages, v_pages, table,
                                        lengths)
    got = pa.paged_decode_attention_kernel(q, k_pages, v_pages, table,
                                           lengths)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2)


def test_kernel_ignores_dead_table_tail(interpret_mode):
    """Stale ids in the dead tail of a table row must not affect the
    output (the index map clamps to the last live page)."""
    rng = np.random.RandomState(2)
    q, k_pages, v_pages, table = _random_case(rng, jnp.float32)
    lengths = jnp.asarray([4, 9, 12, 30], jnp.int32)
    ref = pa.paged_decode_attention_kernel(q, k_pages, v_pages, table,
                                           lengths)
    page = k_pages.shape[1]
    poisoned = np.asarray(table).copy()
    for b, ln in enumerate(np.asarray(lengths)):
        live = (int(ln) + page - 1) // page
        poisoned[b, live:] = 0  # stale/reused page ids
    got = pa.paged_decode_attention_kernel(
        q, k_pages, v_pages, jnp.asarray(poisoned), lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=0, rtol=0)


def test_dispatch_auto_is_xla_off_tpu():
    rng = np.random.RandomState(3)
    q, k_pages, v_pages, table = _random_case(rng, jnp.float32)
    lengths = jnp.asarray([2, 2, 2, 2], jnp.int32)
    auto = pa.paged_decode_attention(q, k_pages, v_pages, table,
                                     lengths)
    xla = pa.paged_decode_attention_xla(q, k_pages, v_pages, table,
                                        lengths)
    assert jax.default_backend() != "tpu"
    np.testing.assert_allclose(np.asarray(auto), np.asarray(xla))


def test_transformer_paged_decode_kernel_equals_xla(interpret_mode):
    """End-to-end: the transformer's paged decode step produces the
    same output under impl='kernel' and impl='xla'."""
    from batch_shipyard_tpu.models import transformer as tfm

    def run(impl):
        cfg = tfm.TransformerConfig(
            vocab_size=128, d_model=64, n_layers=2, n_heads=2,
            d_head=32, d_ff=128, dtype=jnp.float32, decode=True,
            max_decode_len=16, kv_page_size=8, kv_num_pages=16,
            paged_attention_impl=impl)
        model = tfm.TransformerLM(cfg)
        tokens = jnp.asarray([[5], [9]], jnp.int32)
        variables = model.init(jax.random.PRNGKey(0), tokens,
                               positions=jnp.zeros((2, 1), jnp.int32))
        params, cache = variables["params"], variables["cache"]

        # Give the two slots disjoint, non-contiguous physical pages
        # (block tables init to zeros, which would collide both slots
        # onto page 0 and mask indexing bugs).
        def assign_tables(leaf_dict):
            if isinstance(leaf_dict, dict) and "block_table" in \
                    leaf_dict:
                table = jnp.asarray([[3, 7], [11, 5]], jnp.int32)
                return {**leaf_dict, "block_table": table}
            return leaf_dict

        cache = jax.tree_util.tree_map(
            assign_tables, cache,
            is_leaf=lambda x: isinstance(x, dict) and
            "block_table" in x)
        outs = []
        for step in range(3):
            tok = jnp.asarray([[5 + step], [9 + step]], jnp.int32)
            pos = jnp.full((2, 1), step, jnp.int32)
            logits, mutated = model.apply(
                {"params": params, "cache": cache}, tok, positions=pos,
                mutable=["cache"])
            cache = mutated["cache"]
            outs.append(np.asarray(logits))
        return np.stack(outs)

    np.testing.assert_allclose(run("kernel"), run("xla"),
                               atol=1e-5, rtol=1e-5)


def _int8_case(rng, batch=4, heads=4, depth=64, page=8,
               max_blocks=6, num_pages=32):
    from batch_shipyard_tpu.ops.quantization import quantize_int8_rows
    q = jnp.asarray(rng.randn(batch, 1, heads, depth), jnp.float32)
    k_f = jnp.asarray(rng.randn(num_pages, page, heads, depth),
                      jnp.float32)
    v_f = jnp.asarray(rng.randn(num_pages, page, heads, depth),
                      jnp.float32)
    k_pages, k_scales = quantize_int8_rows(k_f)
    v_pages, v_scales = quantize_int8_rows(v_f)
    k_pages, v_pages, k_f, v_f = map(
        _folded, (k_pages, v_pages, k_f, v_f))
    table = jnp.asarray(
        rng.permutation(num_pages)[:batch * max_blocks].reshape(
            batch, max_blocks), jnp.int32)
    lengths = jnp.asarray([1, 7, 23, 48], jnp.int32)
    return (q, k_pages, v_pages, table, lengths, k_scales, v_scales,
            k_f, v_f)


def test_int8_kernel_matches_int8_xla(interpret_mode):
    """The in-kernel per-tile dequant must agree exactly with the
    gathered-slice dequant of the XLA path (same int8 inputs)."""
    rng = np.random.RandomState(23)
    (q, kp, vp, table, lengths, ks, vs, _kf, _vf) = _int8_case(rng)
    got = pa.paged_decode_attention_kernel(
        q, kp, vp, table, lengths, k_scales=ks, v_scales=vs)
    want = pa.paged_decode_attention_xla(
        q, kp, vp, table, lengths, k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_int8_xla_close_to_fp(interpret_mode):
    """int8 paged attention stays within quantization noise of the
    full-precision pages it was quantized from."""
    rng = np.random.RandomState(29)
    (q, kp, vp, table, lengths, ks, vs, k_f, v_f) = _int8_case(rng)
    got = pa.paged_decode_attention_xla(
        q, kp, vp, table, lengths, k_scales=ks, v_scales=vs)
    ref = pa.paged_decode_attention_xla(q, k_f, v_f, table, lengths)
    rel = (np.linalg.norm(np.asarray(got - ref)) /
           np.linalg.norm(np.asarray(ref)))
    assert rel < 0.02, rel

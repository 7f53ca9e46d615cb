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


# ---- the grouped, windowed kernel (gqa_paged_decode) ----

def _grouped_case(rng, dtype, heads, kv_heads, batch=5, depth=64,
                  page=8, entries=12, num_pages=64):
    q = jnp.asarray(rng.randn(batch, 1, heads, depth), dtype)
    k_pages = jnp.asarray(
        rng.randn(num_pages, page, kv_heads * depth), dtype)
    v_pages = jnp.asarray(
        rng.randn(num_pages, page, kv_heads * depth), dtype)
    table = jnp.asarray(
        rng.permutation(num_pages)[:batch * entries].reshape(
            batch, entries), jnp.int32)
    return q, k_pages, v_pages, table


def _masked_oracle(q, k_pages, v_pages, table, lengths, window):
    """Softmax attention over the slot's keys [max(0, L - window), L)
    written out with numpy, position by position through the table."""
    batch, _one, heads, depth = q.shape
    page = k_pages.shape[1]
    kv_heads = k_pages.shape[2] // depth
    out = np.zeros((batch, 1, heads, depth), np.float32)
    for b in range(batch):
        length = int(lengths[b])
        low = max(0, length - window) if window else 0
        if length == 0:
            continue
        rows = [(int(table[b, (p // page) % table.shape[1]]), p % page)
                for p in range(low, length)]
        keys = np.stack([np.asarray(k_pages[i, j], np.float32)
                         for i, j in rows]).reshape(-1, kv_heads, depth)
        values = np.stack([np.asarray(v_pages[i, j], np.float32)
                           for i, j in rows]).reshape(-1, kv_heads,
                                                      depth)
        for h in range(heads):
            kv = h // (heads // kv_heads)
            scores = keys[:, kv] @ np.asarray(q[b, 0, h], np.float32) \
                / np.sqrt(depth)
            probs = np.exp(scores - scores.max())
            out[b, 0, h] = (probs / probs.sum()) @ values[:, kv]
    return out


@pytest.mark.parametrize("window", (0, 20))
@pytest.mark.parametrize("heads,kv_heads", ((4, 4), (14, 2), (4, 2)),
                         ids=("mha", "7to1", "2to1"))
def test_grouped_kernel_matches_the_masked_gather(interpret_mode,
                                                  heads, kv_heads,
                                                  window):
    """MHA, 7 : 1 and 2 : 1 groupings; lengths 0, under, at, one over
    and far over the window (20 keys over pages of 8: its edge lies
    inside a page), the last crossing several chunks of pages."""
    rng = np.random.RandomState(heads + window)
    q, k_pages, v_pages, table = _grouped_case(rng, jnp.float32, heads,
                                               kv_heads)
    lengths = jnp.asarray([0, 5, 20, 21, 93], jnp.int32)
    want = _masked_oracle(q, k_pages, v_pages, table, lengths, window)
    xla = pa.paged_decode_attention_xla_windowed(
        q, k_pages, v_pages, table, lengths, window=window)
    got = pa.gqa_paged_decode_attention_kernel(
        q, k_pages, v_pages, table, lengths, window=window)
    # float32 throughout: the order of the sums alone
    np.testing.assert_allclose(np.asarray(xla)[1:], want[1:],
                               atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(got)[1:], want[1:],
                               atol=2e-6, rtol=2e-6)
    assert not np.asarray(got)[0].any()     # a slot at length 0: zeros
    if not window:
        plain = pa.paged_decode_attention_xla(q, k_pages, v_pages,
                                              table, lengths)
        np.testing.assert_allclose(np.asarray(xla)[1:],
                                   np.asarray(plain)[1:], atol=1e-6)


@pytest.mark.parametrize("lengths", ([3, 24, 25], [33, 57, 100]),
                         ids=("unwrapped", "wrapped"))
def test_a_ring_of_pages_holds_the_windows_keys(interpret_mode,
                                                lengths):
    """A table narrower than the context is a ring: 4 entries for a
    window of 20 over pages of 8 (ceil(20 / 8) + 1). The oracle reads
    position p through entry (p // page) % 4."""
    rng = np.random.RandomState(3)
    q, k_pages, v_pages, table = _grouped_case(
        rng, jnp.float32, 14, 2, batch=3, entries=4, num_pages=16)
    lengths = jnp.asarray(lengths, jnp.int32)
    want = _masked_oracle(q, k_pages, v_pages, table, lengths, 20)
    for fn in (pa.paged_decode_attention_xla_windowed,
               pa.gqa_paged_decode_attention_kernel):
        got = fn(q, k_pages, v_pages, table, lengths, window=20)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-6,
                                   rtol=2e-6)


def test_grouped_kernel_bf16_is_close_to_the_gather(interpret_mode):
    rng = np.random.RandomState(5)
    q, k_pages, v_pages, table = _grouped_case(rng, jnp.bfloat16, 14, 2)
    lengths = jnp.asarray([1, 9, 40, 64, 96], jnp.int32)
    want = pa.paged_decode_attention_xla_windowed(
        q, k_pages, v_pages, table, lengths, window=24)
    got = pa.gqa_paged_decode_attention_kernel(
        q, k_pages, v_pages, table, lengths, window=24)
    # bfloat16 probabilities on both sides, rounded at other points
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=3e-2)


def test_grouped_kernel_with_its_softmax_kept_in_bfloat16(
        interpret_mode):
    """softmax_dtype bfloat16 (a check's control) moves the kernel's
    result by a rounding a chunk of pages, far more than the float32
    kernel differs from the gather, and reaches the kernel through the
    dispatch; the gather rounds its one-pass softmax's terms."""
    rng = np.random.RandomState(7)
    q, k_pages, v_pages, table = _grouped_case(rng, jnp.float32, 14, 2)
    q = 3 * q
    lengths = jnp.asarray([1, 9, 40, 64, 96], jnp.int32)
    args = (q, k_pages, v_pages, table, lengths)
    sound = np.asarray(pa.gqa_paged_decode_attention_kernel(
        *args, window=24))
    low = np.asarray(pa.gqa_paged_decode_attention_kernel(
        *args, window=24, softmax_dtype=jnp.bfloat16))
    assert 1e-3 < np.abs(low - sound).max() < 5e-2
    np.testing.assert_array_equal(low, np.asarray(
        pa.paged_decode_attention(*args, impl="kernel", window=24,
                                  softmax_dtype=jnp.bfloat16)))
    gather = np.asarray(pa.paged_decode_attention(
        *args, impl="xla", window=24, softmax_dtype=jnp.bfloat16))
    assert 1e-3 < np.abs(gather - sound).max() < 5e-2


def test_what_kernel_means_for_a_grouped_pool(interpret_mode,
                                              monkeypatch):
    """impl None keeps a grouped pool on the XLA gather whatever the
    backend; "kernel" MEANS the grouped Pallas kernel; a window takes
    the windowed pair; int8 pages have neither."""
    rng = np.random.RandomState(6)
    q, k_pages, v_pages, table = _grouped_case(rng, jnp.float32, 4, 2)
    lengths = jnp.asarray([1, 9, 40, 64, 96], jnp.int32)
    called = []
    for name in ("paged_decode_attention_kernel",
                 "paged_decode_attention_xla",
                 "gqa_paged_decode_attention_kernel",
                 "paged_decode_attention_xla_windowed"):
        monkeypatch.setattr(
            pa, name, lambda *a, _name=name, **k: called.append(_name))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = (q, k_pages, v_pages, table, lengths)
    pa.paged_decode_attention(*args)
    pa.paged_decode_attention(*args, impl="kernel")
    pa.paged_decode_attention(*args, impl="xla")
    pa.paged_decode_attention(*args, window=8)
    pa.paged_decode_attention(*args, impl="kernel", window=8)
    assert called == ["paged_decode_attention_xla",
                      "gqa_paged_decode_attention_kernel",
                      "paged_decode_attention_xla",
                      "paged_decode_attention_xla_windowed",
                      "gqa_paged_decode_attention_kernel"]
    with pytest.raises(NotImplementedError):
        pa.paged_decode_attention(*args, impl="kernel", k_scales=1,
                                  v_scales=1)
    mha = (q, jnp.tile(k_pages, (1, 1, 2)), jnp.tile(v_pages, (1, 1, 2)),
           table, lengths)
    del called[:]
    pa.paged_decode_attention(*mha)
    pa.paged_decode_attention(*mha, window=8)
    assert called == ["paged_decode_attention_kernel",
                      "paged_decode_attention_xla_windowed"]

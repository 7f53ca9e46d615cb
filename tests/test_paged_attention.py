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


def _mha_road(q, k_pages, v_pages, table, lengths):
    """An MHA pool's one-token call as the dispatch makes it on a TPU:
    the one-program-a-slot kernel (gqa_kernel) at as many K/V heads as
    query heads, its chunk of pages from the pool's width."""
    assert pa.paged_decode_road("kernel", grouped=False) == "gqa_kernel"
    return pa.paged_decode_attention(q, k_pages, v_pages, table,
                                     lengths, impl="kernel")


def _served_mha_case(rng, lengths, heads=32, depth=128, page=64,
                     entries=32):
    """Baichuan's pool as it is served (32 heads of 128, pages of 64,
    a table of 32 entries, bfloat16: 2 pages a chunk), each slot
    holding exactly the pages its length needs and its table's dead
    tail pointing at page 0, as a freed slot's does."""
    need = [-(-length // page) for length in lengths]
    q = jnp.asarray(rng.randn(len(lengths), 1, heads, depth),
                    jnp.bfloat16)
    pools = [jnp.asarray(
        rng.randn(1 + sum(need), page, heads * depth), jnp.bfloat16)
        for _ in range(2)]
    table = np.zeros((len(lengths), entries), np.int32)
    ids = iter(rng.permutation(sum(need)) + 1)
    for b, pages in enumerate(need):
        table[b, :pages] = [next(ids) for _ in range(pages)]
    return q, pools[0], pools[1], jnp.asarray(table)


# (dtype, the case's shape, lengths, tolerance). Pages of 8 and 4
# heads of 64 in float32: a chunk is the whole table of 6 (ragged) or
# 8 of its 20 entries (edges: one key, a chunk's edge at 64 and 128
# keys from both sides, the full table). Then bfloat16 as served, and
# Baichuan's served shape: parked slots of length 1, the edge of a
# 2-page chunk at 128 keys from both sides, the cell's mean context,
# the full 32-page table.
_MHA_CASES = {
    "f32-ragged": (jnp.float32, {}, [1, 5, 23, 48], 2e-6),
    "f32-chunk-edges": (jnp.float32,
                        dict(batch=7, max_blocks=20, num_pages=140),
                        [1, 63, 64, 65, 128, 129, 160], 2e-6),
    "bf16-ragged": (jnp.bfloat16, {}, [3, 8, 17, 41], 2e-2),
    "bf16-served": (jnp.bfloat16, None,
                    [1, 1, 127, 128, 129, 580, 2048], 3e-2),
}


@pytest.mark.parametrize("case", sorted(_MHA_CASES))
def test_mha_road_matches_the_gather(interpret_mode, case):
    dtype, shape, lengths, tol = _MHA_CASES[case]
    rng = np.random.RandomState(len(case))
    if shape is None:
        q, k_pages, v_pages, table = _served_mha_case(rng, lengths)
        assert pa.gqa_chunk_pages(64, 4096, 2, 32) == 2
    else:
        q, k_pages, v_pages, table = _random_case(rng, dtype, **shape)
    lengths = jnp.asarray(lengths, jnp.int32)
    ref = pa.paged_decode_attention_xla(q, k_pages, v_pages, table,
                                        lengths)
    got = _mha_road(q, k_pages, v_pages, table, lengths)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol)


def test_mha_road_ignores_dead_table_tail(interpret_mode):
    """Stale ids in the dead tail of a table row must not affect the
    output (no page past the last live one is fetched)."""
    rng = np.random.RandomState(2)
    q, k_pages, v_pages, table = _random_case(rng, jnp.float32)
    lengths = jnp.asarray([4, 9, 12, 30], jnp.int32)
    ref = _mha_road(q, k_pages, v_pages, table, lengths)
    page = k_pages.shape[1]
    poisoned = np.asarray(table).copy()
    for b, ln in enumerate(np.asarray(lengths)):
        live = (int(ln) + page - 1) // page
        poisoned[b, live:] = 0  # stale/reused page ids
    got = _mha_road(q, k_pages, v_pages, jnp.asarray(poisoned),
                    lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=0, rtol=0)


# (page, channels, bytes a value, table entries) -> pages a chunk:
# the grouped pools served (Nemotron 256, SmallThinker 512,
# Solar-Open2 and K-EXAONE 1,024 channels) keep 8, Baichuan's 4,096
# channels walk 2, a float32 pool half its bfloat16 twin's, a ring or
# table narrower than the chunk bounds it, and one page always goes.
@pytest.mark.parametrize("page,width,itemsize,entries,want", [
    (64, 256, 2, 32, 8), (64, 512, 2, 256, 8), (64, 1024, 2, 32, 8),
    (64, 4096, 2, 32, 2), (64, 4096, 4, 32, 1), (64, 2048, 2, 32, 4),
    (64, 512, 2, 4, 4), (64, 4096, 2, 1, 1), (8, 256, 4, 6, 6),
    (128, 8192, 4, 32, 1)])
def test_the_chunk_comes_from_the_pools_width(page, width, itemsize,
                                              entries, want):
    chunk = pa.gqa_chunk_pages(page, width, itemsize, entries)
    assert chunk == want
    assert 1 <= chunk <= min(pa.GQA_CHUNK_PAGES, entries)
    # K and V, double-buffered: four buffers within the v5e's 16 MiB
    # of scoped VMEM wherever more than one page goes
    assert chunk == 1 or 4 * chunk * page * width * itemsize <= 2 ** 22


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


# (query heads, K/V heads, the pool's and table's shape, lengths).
# The first three: MHA, 7 : 1 and 2 : 1 groupings over pages of 8;
# lengths 0, under, at, one over and far over the window (20 keys: its
# edge lies inside a page), the last crossing several chunks of pages.
# The last two: the hybrid configurations' attention as they serve it
# (64 query over 8 K/V heads and 32 over 2, heads of 128, pages of 64,
# a table of 32 entries = 2,048 positions): a slot of length 0, one
# key, a page's edge from both sides, the cell's mean context (one
# whole chunk of GQA_CHUNK_PAGES and the head of a second), a full
# table.
_SMALL = dict(shape={}, lengths=[0, 5, 20, 21, 93])
_SERVED = dict(shape=dict(batch=7, depth=128, page=64, entries=32,
                          num_pages=224),
               lengths=[0, 1, 63, 64, 65, 520, 2048])
_GROUPINGS = {"mha": (4, 4, _SMALL), "7to1": (14, 2, _SMALL),
              "2to1": (4, 2, _SMALL), "solaropen2": (64, 8, _SERVED),
              "nemotron3nano": (32, 2, _SERVED)}


@pytest.mark.parametrize("window", (0, 20))
@pytest.mark.parametrize("grouping", sorted(_GROUPINGS))
def test_grouped_kernel_matches_the_masked_gather(interpret_mode,
                                                  grouping, window):
    heads, kv_heads, case = _GROUPINGS[grouping]
    rng = np.random.RandomState(heads + window)
    q, k_pages, v_pages, table = _grouped_case(
        rng, jnp.float32, heads, kv_heads, **case["shape"])
    lengths = jnp.asarray(case["lengths"], jnp.int32)
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
        # the gather the hybrid configurations decoded by until PR 41
        plain = pa.paged_decode_attention_xla(q, k_pages, v_pages,
                                              table, lengths)
        np.testing.assert_allclose(np.asarray(xla)[1:],
                                   np.asarray(plain)[1:], atol=1e-6)
        np.testing.assert_allclose(np.asarray(got)[1:],
                                   np.asarray(plain)[1:], atol=2e-6,
                                   rtol=2e-6)


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


@pytest.mark.parametrize("grouping,window,lengths", (
    ("7to1", 24, [1, 9, 40, 64, 96]),
    ("solaropen2", 0, _SERVED["lengths"]),
    ("nemotron3nano", 0, _SERVED["lengths"])))
def test_grouped_kernel_bf16_is_close_to_the_gather(interpret_mode,
                                                    grouping, window,
                                                    lengths):
    """bfloat16 pages, as served: the kernel against the gather of
    the same dispatch (without a window, the plain one the hybrid
    configurations ran)."""
    heads, kv_heads, case = _GROUPINGS[grouping]
    rng = np.random.RandomState(5)
    q, k_pages, v_pages, table = _grouped_case(
        rng, jnp.bfloat16, heads, kv_heads, **case["shape"])
    lengths = jnp.asarray(lengths, jnp.int32)
    args = (q, k_pages, v_pages, table, lengths)
    want = pa.paged_decode_attention(*args, impl="xla", window=window)
    got = pa.paged_decode_attention(*args, impl="kernel", window=window)
    live = np.asarray(lengths) > 0          # length 0: the contract's
    # bfloat16 probabilities on both sides, rounded at other points
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live],
        np.asarray(want, np.float32)[live], atol=3e-2)
    assert not np.asarray(got, np.float32)[~live].any()


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


_K, _G, _X, _W = "kernel", "gqa_kernel", "xla", "xla_windowed"
_NO = NotImplementedError
_ROAD_FUNCTIONS = {_K: "paged_decode_attention_kernel",
                   _G: "gqa_paged_decode_attention_kernel",
                   _X: "paged_decode_attention_xla",
                   _W: "paged_decode_attention_xla_windowed"}
# The dispatch's whole table, written out: (pool, window, pages) ->
# what runs under (tpu None, tpu "kernel", tpu "xla", cpu None,
# cpu "kernel", cpu "xla"). impl None is the kernel on a TPU and the
# gather elsewhere for EVERY pool; the kernel of bf16/f32 pages is
# the one-program-a-slot one whatever the pool and the layer (an MHA
# pool is its case of as many K/V heads as query heads); int8 pages
# of an MHA pool keep the (slot, table entry) grid kernel, the only
# one that reads scales; where no kernel can serve (a grouped int8
# pool) None falls back to the gather and "kernel" raises; the
# windowed gather reads no scales either.
_DISPATCH = {
    ("mha", 0, "bf16"): (_G, _G, _X, _X, _G, _X),
    ("mha", 0, "int8"): (_K, _K, _X, _X, _K, _X),
    ("grouped", 0, "bf16"): (_G, _G, _X, _X, _G, _X),
    ("grouped", 0, "int8"): (_X, _NO, _X, _X, _NO, _X),
    ("mha", 8, "bf16"): (_G, _G, _W, _W, _G, _W),
    ("mha", 8, "int8"): (_NO,) * 6,
    ("grouped", 8, "bf16"): (_G, _G, _W, _W, _G, _W),
    ("grouped", 8, "int8"): (_NO,) * 6,
}
_DISPATCH_CASES = [
    (backend, pool, window, impl, pages, row[3 * b + i])
    for (pool, window, pages), row in _DISPATCH.items()
    for b, backend in enumerate(("tpu", "cpu"))
    for i, impl in enumerate((None, "kernel", "xla"))]


@pytest.mark.parametrize(
    "backend,pool,window,impl,pages,want", _DISPATCH_CASES,
    ids=[f"{b}-{pool}-w{w}-{impl}-{pages}"
         for b, pool, w, impl, pages, _want in _DISPATCH_CASES])
def test_what_kernel_means_for_a_grouped_pool(monkeypatch, backend,
                                              pool, window, impl,
                                              pages, want):
    """One case of the selection rule's table: the implementation the
    dispatch calls (or the error it raises), and the same answer from
    the serving report's function for an engine's config of that
    pool, which asks the same rule (paged_decode_road)."""
    from batch_shipyard_tpu.models import transformer as tfm
    from batch_shipyard_tpu.workloads import serve

    rng = np.random.RandomState(6)
    kv_heads = 2 if pool == "grouped" else 4
    q, k_pages, v_pages, table = _grouped_case(rng, jnp.float32, 4,
                                               kv_heads)
    lengths = jnp.asarray([1, 9, 40, 64, 96], jnp.int32)
    called, named = [], []
    for name in _ROAD_FUNCTIONS.values():
        monkeypatch.setattr(
            pa, name, lambda *a, _name=name, **k: (
                called.append(_name), named.append(k.get("name", ""))))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    scales = {"k_scales": 1, "v_scales": 1} if pages == "int8" else {}
    config = tfm.TransformerConfig(
        vocab_size=32, d_model=16, n_layers=2, n_heads=4,
        n_kv_heads=kv_heads, d_head=4, d_ff=32,
        paged_attention_impl=impl, layer_windows=(window, window),
        kv_cache_dtype="int8" if pages == "int8" else None)

    def dispatch():
        return pa.paged_decode_attention(
            q, k_pages, v_pages, table, lengths, impl=impl,
            window=window, **scales)

    if want is _NO:
        with pytest.raises(NotImplementedError):
            dispatch()
        with pytest.raises(NotImplementedError):
            serve.paged_decode_impl(config)
        assert not called
        return
    dispatch()
    assert called == [_ROAD_FUNCTIONS[want]]
    assert serve.paged_decode_impl(config) == want
    # the kernel's device events: an MHA pool's one-token call keeps
    # its caller's scope for a name (a trace's reader knows Baichuan's
    # by attn._decode_attend_paged), every other its own
    if want == _G:
        assert named == [None if (pool, window) == ("mha", 0)
                         else pa.GQA_KERNEL_NAME]


def test_the_report_names_each_kind_of_layer(monkeypatch):
    """A stack of full and window layers over a grouped pool: one name
    a kind of layer, as the dispatch decides each; an unknown impl is
    refused by the rule itself."""
    from batch_shipyard_tpu.models import transformer as tfm
    from batch_shipyard_tpu.workloads import serve

    config = tfm.TransformerConfig(
        vocab_size=32, d_model=16, n_layers=2, n_heads=4, n_kv_heads=2,
        d_head=4, d_ff=32, layer_windows=(0, 8))
    assert serve.paged_decode_impl(config) == "xla+xla_windowed"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert serve.paged_decode_impl(config) == "gqa_kernel"
    with pytest.raises(ValueError, match="unknown paged attention"):
        pa.paged_decode_road("pallas", grouped=True)


# ---- a slot without a request: length 0 on every road ----

def _mha_parked(rng, int8=False):
    if int8:
        q, kp, vp, table, _lengths, ks, vs, _kf, _vf = _int8_case(rng)
        return (q, kp, vp, table), {"k_scales": ks, "v_scales": vs}
    return _random_case(rng, jnp.float32), {}


def _grouped_parked(rng, entries=12, positions=1):
    q, k_pages, v_pages, table = _grouped_case(
        rng, jnp.float32, 14, 2, batch=4, entries=entries)
    if positions > 1:
        q = jnp.asarray(rng.randn(4, positions, 14, 64), jnp.float32)
    return (q, k_pages, v_pages, table), {}


def _served_parked(rng):
    """Baichuan's served shape: four seated slots among four parked."""
    return _served_mha_case(rng, [1, 1, 580, 1, 129, 1, 1, 64]), {}


# case -> (the call's road, impl, window, how the case is made, the
# lengths an UNMASKED call is handed, the step's live mask). Every
# road of paged_decode_road's table, and the one-program-a-slot kernel
# (interpret mode) at an MHA pool, a grouped pool, a ring (a table of
# 4 entries under a window of 20) and a two-position verify block. A
# parked slot's unmasked length is 1 (one token a step) or 2 (a verify
# block): what the cursor parked at 0 plus the step's rows gives. In
# every case one LIVE slot has just that length too (its first key
# from an empty cache): the mask, not the length, says which is which.
_PARKED_CASES = {
    "xla-mha": ("xla", "xla", 0, _mha_parked,
                [1, 1, 23, 1], [True, False, True, False]),
    "xla-grouped": ("xla", "xla", 0, _grouped_parked,
                    [1, 40, 1, 1], [False, True, True, False]),
    "xla-int8": ("xla", "xla", 0,
                 lambda rng: _mha_parked(rng, int8=True),
                 [1, 1, 23, 48], [True, False, True, True]),
    "int8-kernel": ("kernel", "kernel", 0,
                    lambda rng: _mha_parked(rng, int8=True),
                    [1, 1, 23, 48], [False, True, True, True]),
    "xla_windowed-window": ("xla_windowed", "xla", 20, _grouped_parked,
                            [1, 1, 93, 21], [False, True, True, True]),
    "xla_windowed-ring": ("xla_windowed", "xla", 20,
                          lambda rng: _grouped_parked(rng, entries=4),
                          [1, 57, 1, 100], [True, True, False, True]),
    "xla_windowed-verify": (
        "xla_windowed", "xla", 0,
        lambda rng: _grouped_parked(rng, positions=2),
        [2, 2, 41, 2], [False, True, True, False]),
    "gqa_kernel-mha": ("gqa_kernel", "kernel", 0, _mha_parked,
                       [1, 1, 23, 1], [True, False, True, False]),
    "gqa_kernel-mha-served": ("gqa_kernel", "kernel", 0, _served_parked,
                              [1, 1, 580, 1, 129, 1, 1, 64],
                              [False, True, True, False, True, False,
                               False, True]),
    "gqa_kernel-grouped": ("gqa_kernel", "kernel", 0, _grouped_parked,
                           [1, 40, 1, 1], [False, True, True, False]),
    "gqa_kernel-ring": ("gqa_kernel", "kernel", 20,
                        lambda rng: _grouped_parked(rng, entries=4),
                        [1, 57, 1, 100], [True, True, False, True]),
    "gqa_kernel-verify": (
        "gqa_kernel", "kernel", 0,
        lambda rng: _grouped_parked(rng, positions=2),
        [2, 2, 41, 2], [False, True, True, False]),
    "gqa_kernel-verify-ring": (
        "gqa_kernel", "kernel", 16,
        lambda rng: _grouped_parked(rng, entries=4, positions=2),
        [2, 2, 41, 2], [True, False, True, False]),
}


@pytest.mark.parametrize("case", sorted(_PARKED_CASES))
def test_a_parked_slot_yields_zeros_and_moves_no_live_row(
        interpret_mode, case):
    """The step's live mask reaches a paged decode call as length 0
    (Attention._decode_attend_paged): the parked rows come back zero on
    EVERY road, the kernels' and the gathers' alike, and the live rows
    bit for bit what the call returns when the parked slots are handed
    the length their parked cursor gives. A live slot whose cursor was
    0 (length 1, or 2 in a verify block) still attends its own keys:
    its newest query's output is not zero, and with one key it is that
    key's value row."""
    road, impl, window, make, lengths, live = _PARKED_CASES[case]
    args, scales = make(np.random.RandomState(len(case)))
    q, k_pages, v_pages, table = args
    grouped = k_pages.shape[2] != q.shape[2] * q.shape[3]
    assert pa.paged_decode_road(
        impl, grouped=grouped, window=window, int8=bool(scales),
        positions=q.shape[1]) == road
    live = np.asarray(live)
    lengths = np.asarray(lengths, np.int32)

    def call(handed):
        return np.asarray(pa.paged_decode_attention(
            q, k_pages, v_pages, table, jnp.asarray(handed), impl=impl,
            window=window, **scales), np.float32)

    unmasked = call(lengths)
    got = call(np.where(live, lengths, 0).astype(np.int32))
    assert got.shape == q.shape
    np.testing.assert_array_equal(got[live], unmasked[live])
    assert not got[~live].any()
    assert unmasked[~live].any()        # the parent's call did attend
    # the live slots at a fresh cursor: their first key, attended
    fresh = live & (lengths == q.shape[1])
    assert fresh.any()
    depth = q.shape[3]
    kv_heads = k_pages.shape[2] // depth
    for b in np.flatnonzero(fresh):
        assert got[b, -1].any()
        value = np.asarray(v_pages[int(table[b, 0]), 0], np.float32)
        if scales:
            value = value.reshape(kv_heads, depth) * np.asarray(
                scales["v_scales"][int(table[b, 0]), 0])[:, None]
        per_head = np.repeat(value.reshape(kv_heads, depth),
                             q.shape[2] // kv_heads, axis=0)
        # query position 0 of the slot sees key 0 alone
        np.testing.assert_allclose(
            got[b, 0], per_head, rtol=1e-2 if q.dtype == jnp.bfloat16
            else 1e-5, atol=1e-2 if q.dtype == jnp.bfloat16 else 1e-5)


# a model of each paged kind: an MHA pool, a grouped pool, a grouped
# pool whose second layer is a window layer over a ring
_LIVE_MODELS = {
    "mha": dict(n_heads=2, d_head=32),
    "grouped": dict(n_heads=4, n_kv_heads=2, d_head=16),
    "ring": dict(n_heads=4, n_kv_heads=2, d_head=16,
                 layer_windows=(0, 12)),
    "int8": dict(n_heads=2, d_head=32, kv_cache_dtype="int8"),
}


@pytest.mark.parametrize("impl", ("xla", "kernel"))
@pytest.mark.parametrize("kind", sorted(_LIVE_MODELS))
def test_the_models_live_mask_parks_a_slot_at_any_cursor(
        interpret_mode, kind, impl):
    """model.apply(..., live=mask) from an EMPTY paged cache, every
    cursor at 0: the live slots' logits are bit for bit the unmasked
    call's (a live slot at cursor 0 attends its one key: parked is
    what the mask says, never what the cursor reads), the parked
    slot's are not (its attention came back zero), and the cache (rows
    written, cursors advanced) is the same either way."""
    from batch_shipyard_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, d_ff=128,
        dtype=jnp.float32, decode=True, max_decode_len=32,
        kv_page_size=8, kv_num_pages=16, paged_attention_impl=impl,
        **_LIVE_MODELS[kind])
    model = tfm.TransformerLM(cfg)
    tokens = jnp.asarray([[5], [9], [17]], jnp.int32)
    positions = jnp.zeros((3, 1), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens,
                           positions=positions)
    params = variables["params"]

    def tables(node):
        # (init ran one forward: the cursors back to an empty cache's)
        if isinstance(node, dict) and "length" in node:
            node = {**node, "length": jnp.zeros_like(node["length"])}
        if isinstance(node, dict) and "block_table" in node:
            return {**node, "block_table": jnp.asarray(
                [[3, 7, 1, 2], [11, 5, 4, 6], [9, 8, 10, 12]],
                jnp.int32)}
        return node

    cache = jax.tree_util.tree_map(
        tables, variables["cache"],
        is_leaf=lambda x: isinstance(x, dict) and "length" in x)
    live = np.asarray([True, False, True])
    outs = {}
    for name, mask in (("unmasked", None), ("masked", jnp.asarray(live))):
        stepped = cache
        logits = []
        for step in range(2):
            out, mutated = model.apply(
                {"params": params, "cache": stepped}, tokens + step,
                positions=positions + step, live=mask,
                mutable=["cache"])
            stepped = mutated["cache"]
            logits.append(np.asarray(out))
        outs[name] = (np.stack(logits), stepped)
    (plain, plain_cache), (masked, masked_cache) = (
        outs["unmasked"], outs["masked"])
    np.testing.assert_array_equal(masked[:, live], plain[:, live])
    assert np.abs(masked[:, ~live] - plain[:, ~live]).max() > 1e-4
    for (path, want), got in zip(
            jax.tree_util.tree_leaves_with_path(plain_cache),
            jax.tree_util.tree_leaves(masked_cache)):
        key = path[-1].key
        if key in ("length", "block_table"):
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want))
            if key == "length":
                assert list(np.asarray(got)) == [2, 2, 2]


# ---- a block whose queries all see all keys (a model that generates
# ---- by diffusion over blocks: paged_decode_attention's visible block)

@pytest.mark.parametrize("grouping", ("7to1", "mha", "sdar"))
def test_a_block_whose_queries_all_see_all_keys(interpret_mode,
                                                grouping):
    """Four query positions a slot, every one of which sees all
    ``length`` keys (the block's own four among them): the kernel and
    its XLA twin against four ONE-position calls at the same length,
    which is what such a block is; the verify block's mask (query r up
    to its own key) is another result. "sdar": the cell's attention as
    it serves it (32 query over 4 K/V heads of 128, pages of 64)."""
    heads, kv_heads, case = {**_GROUPINGS, "sdar": (32, 4, _SERVED)}[
        grouping]
    rng = np.random.RandomState(heads)
    q, k_pages, v_pages, table = _grouped_case(
        rng, jnp.float32, heads, kv_heads, **case["shape"])
    q = jnp.asarray(rng.randn(q.shape[0], 4, heads, q.shape[3]),
                    jnp.float32)
    lengths = jnp.asarray([0] + [max(4, n) for n in case["lengths"][1:]],
                          jnp.int32)
    want = np.concatenate([np.asarray(
        pa.paged_decode_attention_xla_windowed(
            q[:, r:r + 1], k_pages, v_pages, table, lengths))
        for r in range(4)], axis=1)
    xla = pa.paged_decode_attention_xla_windowed(
        q, k_pages, v_pages, table, lengths, block=4)
    got = pa.gqa_paged_decode_attention_kernel(
        q, k_pages, v_pages, table, lengths, block=4)
    np.testing.assert_allclose(np.asarray(xla), want, atol=2e-6,
                               rtol=2e-6)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6,
                               rtol=2e-6)
    assert not np.asarray(got)[0].any()     # a slot at length 0: zeros
    verify = pa.gqa_paged_decode_attention_kernel(
        q, k_pages, v_pages, table, lengths)
    assert np.abs(np.asarray(verify) - want)[1:, :3].max() > 1e-3
    # the last position's mask is the same in both
    np.testing.assert_allclose(np.asarray(verify)[:, 3], want[:, 3],
                               atol=2e-6, rtol=2e-6)
    # through the dispatch, by the road it names
    assert pa.paged_decode_road("kernel", grouped=heads != kv_heads,
                                positions=4) == "gqa_kernel"
    np.testing.assert_allclose(np.asarray(pa.paged_decode_attention(
        q, k_pages, v_pages, table, lengths, impl="kernel",
        block=4)), want, atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(pa.paged_decode_attention(
        q, k_pages, v_pages, table, lengths, impl="xla",
        block=4)), want, atol=2e-6, rtol=2e-6)


def test_a_block_of_all_keys_takes_no_window():
    q = jnp.zeros((1, 4, 4, 64), jnp.float32)
    pool = jnp.zeros((8, 8, 128), jnp.float32)
    table = jnp.zeros((1, 4), jnp.int32)
    for call in (pa.gqa_paged_decode_attention_kernel,
                 pa.paged_decode_attention_xla_windowed):
        with pytest.raises(NotImplementedError, match="window"):
            call(q, pool, pool, table, jnp.asarray([8]), window=5,
                 block=4)
        with pytest.raises(ValueError, match="whole number"):
            call(q, pool, pool, table, jnp.asarray([8]), block=3)


# ---- two blocks a slot (the block pass that commits a finished block
# ---- beside the next one's first denoise pass): block-causal BETWEEN
# ---- the call's blocks, and a dead second block passed over

def _by_hand(q, k_pages, v_pages, table, lengths, block):
    """Softmax attention with the mask written out: query r of S at
    key position L - S + r sees key j iff j < L - S + block *
    (r // block + 1); float64, a slot and a head at a time."""
    batch, seq, heads, depth = q.shape
    kv_heads = k_pages.shape[2] // depth
    group = heads // kv_heads
    out = np.zeros(q.shape, np.float64)
    k_pages, v_pages, q = (np.asarray(x, np.float64)
                           for x in (k_pages, v_pages, q))
    for b in range(batch):
        length = int(lengths[b])
        if not length:
            continue
        keys = k_pages[np.asarray(table)[b]].reshape(
            -1, kv_heads, depth)[:length]
        values = v_pages[np.asarray(table)[b]].reshape(
            -1, kv_heads, depth)[:length]
        for r in range(seq):
            upto = length - seq + block * (r // block + 1)
            for h in range(heads):
                scores = keys[:upto, h // group] @ q[b, r, h] / \
                    np.sqrt(depth)
                weights = np.exp(scores - scores.max())
                out[b, r, h] = weights @ values[:upto, h // group] / \
                    weights.sum()
    return out


@pytest.mark.parametrize("block", (1, 4, 8))
@pytest.mark.parametrize("grouping", ("2to1", "mha"))
def test_the_visible_block_of_a_call_of_eight_positions(
        interpret_mode, grouping, block):
    """Eight query positions a slot under a visible block of 1 (each
    query the keys up to its own: a verify block), 4 (two blocks,
    block-causal between them) and 8 (all see all): the kernel and its
    XLA twin against the mask written out by hand, a slot of length 0
    between seated ones, lengths on and across a chunk's edge (64
    keys)."""
    heads, kv_heads, case = _GROUPINGS[grouping]
    rng = np.random.RandomState(heads + block)
    q, k_pages, v_pages, table = _grouped_case(
        rng, jnp.float32, heads, kv_heads, **case["shape"])
    q = jnp.asarray(rng.randn(q.shape[0], 8, heads, q.shape[3]),
                    jnp.float32)
    lengths = jnp.asarray([8, 64, 0, 68, 93], jnp.int32)
    want = _by_hand(q, k_pages, v_pages, table, lengths, block)
    for call in (pa.gqa_paged_decode_attention_kernel,
                 pa.paged_decode_attention_xla_windowed):
        got = np.asarray(call(q, k_pages, v_pages, table, lengths,
                              block=block))
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
        assert not got[2].any()
    if block == 1:      # no block given: the same call
        np.testing.assert_array_equal(
            np.asarray(pa.gqa_paged_decode_attention_kernel(
                q, k_pages, v_pages, table, lengths)),
            np.asarray(pa.gqa_paged_decode_attention_kernel(
                q, k_pages, v_pages, table, lengths, block=1)))


@pytest.mark.parametrize("block", (4, 1))
@pytest.mark.parametrize("grouping", ("2to1", "mha", "sdar"))
def test_a_slots_dead_second_block_is_passed_over(interpret_mode,
                                                  grouping, block):
    """Two blocks of four positions a slot and ``live_positions``: a
    slot with all eight live is the block-causal call; one with four
    (a plain slot of the block pass: its second block is filler) gives
    for its first block what the ONE-block call gives four keys
    earlier, and zeros for the rest, as a slot of length 0 does for
    all; kernel and twin, and through the dispatch. ``block`` 1: the
    same eight positions under the plain causal mask (the cell's
    control that keeps the mask causal inside a block)."""
    heads, kv_heads, case = {**_GROUPINGS, "sdar": (32, 4, _SERVED)}[
        grouping]
    rng = np.random.RandomState(heads)
    q, k_pages, v_pages, table = _grouped_case(
        rng, jnp.float32, heads, kv_heads, **case["shape"])
    batch = q.shape[0]
    q = jnp.asarray(rng.randn(batch, 8, heads, q.shape[3]), jnp.float32)
    lengths = jnp.asarray(([8, 64, 0, 68, 93, 72, 520] if batch == 7
                           else [8, 64, 0, 68, 93]), jnp.int32)
    live = jnp.asarray([8, 4, 0, 4, 8, 4, 8][:batch], jnp.int32)
    both = np.asarray(pa.paged_decode_attention_xla_windowed(
        q, k_pages, v_pages, table, lengths, block=block))
    first = np.asarray(pa.paged_decode_attention_xla_windowed(
        q[:, :4], k_pages, v_pages, table,
        jnp.maximum(lengths - 4, 0), block=block))
    want = np.where((np.asarray(live) == 8)[:, None, None, None], both,
                    np.concatenate([first, np.zeros_like(first)], axis=1))
    want[np.asarray(live) == 0] = 0.0
    for impl in ("kernel", "xla"):
        got = np.asarray(pa.paged_decode_attention(
            q, k_pages, v_pages, table, lengths, impl=impl,
            block=block, live_positions=live))
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=2e-6)
        assert not got[np.asarray(live) == 4][:, 4:].any()


def test_live_positions_are_of_two_halves_of_whole_blocks_and_tiles():
    pool = jnp.zeros((8, 8, 128), jnp.float32)
    table = jnp.zeros((1, 4), jnp.int32)
    live = jnp.asarray([4], jnp.int32)
    for positions, heads in ((4, 4), (8, 2)):
        with pytest.raises(ValueError, match="two halves a slot"):
            pa.gqa_paged_decode_attention_kernel(
                jnp.zeros((1, positions, heads, 64), jnp.float32), pool,
                pool, table, jnp.asarray([8]), block=4,
                live_positions=live)


@pytest.mark.parametrize("bidirectional", (True, False))
@pytest.mark.parametrize("impl", ("xla", "kernel"))
def test_the_models_two_block_insert_crosses_a_pages_edge(
        interpret_mode, impl, bidirectional):
    """A block-diffusion model's paged insert of TWO blocks from a
    cursor one block short of a page's edge: the second block's rows
    land at the head of the slot's next page, the first block sees
    nothing of them (its logits are the one-block insert's), a slot
    whose second block is dead (int32 ``live``: 4 of 8 positions) has
    the same first block, and ``head_rows`` picks the rows the head
    runs over. ``bidirectional`` False: the control's model, whose mask
    stays causal inside a block, through the same calls."""
    from batch_shipyard_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, d_ff=128, n_heads=4,
        n_kv_heads=2, d_head=16, dtype=jnp.float32, decode=True,
        max_decode_len=32, kv_page_size=8, kv_num_pages=16,
        spec_window=8, paged_attention_impl=impl,
        block_diffusion=tfm.BlockDiffusion(
            block=4, steps=4, mask_id=127, bidirectional=bidirectional))
    model = tfm.TransformerLM(cfg)
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(1, 120, (2, 8)), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens[:, :1],
                           positions=jnp.zeros((2, 1), jnp.int32))
    params = variables["params"]
    table = jnp.asarray([[3, 7, 1, 2, 13], [11, 5, 4, 6, 14]], jnp.int32)

    def seated(node):
        if isinstance(node, dict) and "length" in node:
            return {**node, "length": jnp.zeros_like(node["length"]),
                    "block_table": table}
        return node

    cache = jax.tree_util.tree_map(
        seated, variables["cache"],
        is_leaf=lambda x: isinstance(x, dict) and "length" in x)

    def fed(cache, tokens, start, **kwargs):
        positions = start + jnp.arange(tokens.shape[1])[None] + \
            jnp.zeros((2, 1), jnp.int32)
        out, mutated = model.apply(
            {"params": params, "cache": cache}, tokens,
            positions=positions, mutable=["cache"], **kwargs)
        return np.asarray(out), mutated["cache"]

    # a block of context, then the cursor stands at 4: blocks at 4..7
    # (page 0 of the slot) and 8..11 (its page 1)
    _, cache = fed(cache, tokens[:, :4], 0)
    one, _ = fed(cache, tokens[:, 4:], 4)
    assert one.shape == (2, 4, 128)
    two, after = fed(cache, jnp.concatenate(
        [tokens[:, 4:], jnp.full((2, 4), 127, jnp.int32)], axis=1), 4)
    np.testing.assert_allclose(two[:, :4], one, atol=1e-5)
    layer = after["layer_0"]["attn"]
    assert list(np.asarray(layer["length"])) == [12, 12]
    for b in range(2):
        # the slot's second page holds the second block's four rows
        assert np.asarray(layer["k_pages"])[table[b, 1], :4].any()
        assert not np.asarray(layer["k_pages"])[table[b, 1], 4:].any()
    dead, _ = fed(cache, jnp.concatenate(
        [tokens[:, 4:], jnp.full((2, 4), 127, jnp.int32)], axis=1), 4,
        live=jnp.asarray([8, 4], jnp.int32),
        head_rows=jnp.asarray([[4, 5, 6, 7], [0, 1, 2, 3]], jnp.int32))
    np.testing.assert_allclose(dead[0], two[0, 4:], atol=1e-5)
    np.testing.assert_allclose(dead[1], one[1], atol=1e-5)

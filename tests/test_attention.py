"""Attention kernel correctness: blockwise and ring vs the reference
oracle, on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from batch_shipyard_tpu.ops import attention as attn
from batch_shipyard_tpu.ops import ring_attention as ring
from batch_shipyard_tpu.parallel import mesh as mesh_mod


def make_qkv(batch=2, seq=256, heads=4, depth=64, seed=0,
             dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    shape = (batch, seq, heads, depth)
    q = jnp.asarray(rng.randn(*shape), dtype) * 0.1
    k = jnp.asarray(rng.randn(*shape), dtype) * 0.1
    v = jnp.asarray(rng.randn(*shape), dtype) * 0.1
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_blockwise_matches_reference(causal):
    q, k, v = make_qkv()
    expected = attn.mha_reference(q, k, v, causal=causal)
    got = attn.blockwise_mha(q, k, v, causal=causal, block_size=64)
    np.testing.assert_allclose(got, expected, atol=2e-5, rtol=2e-5)


def test_blockwise_gradients_match_reference():
    q, k, v = make_qkv(seq=128)

    def loss_ref(q, k, v):
        return jnp.sum(attn.mha_reference(q, k, v, causal=True) ** 2)

    def loss_blk(q, k, v):
        return jnp.sum(attn.blockwise_mha(q, k, v, causal=True,
                                          block_size=32) ** 2)

    grads_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    grads_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
    for gr, gb in zip(grads_ref, grads_blk):
        np.testing.assert_allclose(gb, gr, atol=5e-5, rtol=5e-4)


def test_offset_blocks_match_full():
    """Computing the second half of queries with q_offset equals the
    second half of the full computation (the ring invariant)."""
    q, k, v = make_qkv(seq=128)
    full = attn.mha_reference(q, k, v, causal=True)
    half = attn.blockwise_mha(q[:, 64:], k, v, causal=True,
                              block_size=64, q_offset=64, kv_offset=0)
    np.testing.assert_allclose(half, full[:, 64:], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ring_attention_matches_reference(causal, sp):
    mesh = mesh_mod.make_mesh(mesh_mod.auto_axis_sizes(8, sp=sp))
    q, k, v = make_qkv(batch=8, seq=256, heads=4, depth=64)
    expected = attn.mha_reference(q, k, v, causal=causal)
    got = ring.ring_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_ring_attention_differentiable():
    mesh = mesh_mod.make_mesh(mesh_mod.auto_axis_sizes(8, sp=4))
    q, k, v = make_qkv(batch=2, seq=128, heads=2, depth=32)

    def loss_ring(q, k, v):
        return jnp.sum(ring.ring_attention(q, k, v, mesh) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attn.mha_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gg in zip(g_ref, g_ring):
        np.testing.assert_allclose(np.asarray(gg), np.asarray(gr),
                                   atol=5e-5, rtol=5e-4)


def test_flash_attention_interpret_mode():
    """Pallas kernel numerics via the interpreter (no TPU needed)."""
    from batch_shipyard_tpu.ops.attention import _flash_forward
    import jax.experimental.pallas as pl  # noqa: F401
    q, k, v = make_qkv(batch=1, seq=256, heads=2, depth=64)
    expected = attn.mha_reference(q, k, v, causal=True)
    from jax.experimental.pallas import tpu as pltpu
    with pltpu.force_tpu_interpret_mode():
        got = _flash_forward(q, k, v, True, 128, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_attention_dispatch():
    q, k, v = make_qkv(seq=64)
    out = attn.attention(q, k, v, impl="blockwise", block_size=32)
    assert out.shape == q.shape
    with pytest.raises(ValueError):
        attn.attention(q, k, v, impl="bogus")


def test_flash_backward_matches_reference_interpret():
    """Grad parity of the hand-written pallas backward kernels vs the
    reference oracle (interpret mode, fp32 — exact math check)."""
    from jax.experimental.pallas import tpu as pltpu
    q, k, v = make_qkv(batch=1, seq=256, heads=2, depth=64)
    g = jnp.asarray(
        np.random.RandomState(7).randn(*q.shape), jnp.float32) * 0.1
    with pltpu.force_tpu_interpret_mode():
        for causal in (True, False):
            def loss_flash(q, k, v):
                return jnp.sum(attn.flash_attention(
                    q, k, v, causal, 128, 128) * g)

            def loss_ref(q, k, v):
                return jnp.sum(attn.mha_reference(q, k, v, causal) * g)

            grads_flash = jax.grad(loss_flash,
                                   argnums=(0, 1, 2))(q, k, v)
            grads_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
            for gf, gr in zip(grads_flash, grads_ref):
                np.testing.assert_allclose(
                    np.asarray(gf), np.asarray(gr), atol=2e-5,
                    rtol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.slow
def test_flash_ring_merge_algorithm_matches_reference(causal):
    """The flash-ring building blocks — flash_attention_with_lse,
    masked_attention_block, merge_attention_blocks, and the 3-case
    (masked/diagonal/full) selection — reproduce exact attention when
    the ring is simulated shard by shard. (Pallas interpret mode
    inside shard_map aborts on CPU, so the shard_map wiring itself is
    covered by the XLA-impl ring tests; this validates the flash
    algorithm.)"""
    from jax.experimental.pallas import tpu as pltpu
    sp = 4
    q, k, v = make_qkv(batch=2, seq=512, heads=2, depth=64)
    t_local = 512 // sp
    expected = attn.mha_reference(q, k, v, causal=causal)
    with pltpu.force_tpu_interpret_mode():
        outs = []
        for my in range(sp):
            q_s = q[:, my * t_local:(my + 1) * t_local]
            o_acc, lse_acc = attn.masked_attention_block(q_s)
            for src_idx in range(sp):
                k_s = k[:, src_idx * t_local:(src_idx + 1) * t_local]
                v_s = v[:, src_idx * t_local:(src_idx + 1) * t_local]
                if causal and src_idx > my:
                    o_s, lse_s = attn.masked_attention_block(q_s)
                elif causal and src_idx == my:
                    o_s, lse_s = attn.flash_attention_with_lse(
                        q_s, k_s, v_s, True)
                else:
                    o_s, lse_s = attn.flash_attention_with_lse(
                        q_s, k_s, v_s, False)
                o_acc, lse_acc = attn.merge_attention_blocks(
                    o_acc, lse_acc, o_s, lse_s)
            outs.append(o_acc)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


# ---------------- in-kernel int8 dense decode -------------------------

def _int8_cache(batch=6, t_len=64, heads=4, depth=64, seed=11):
    from batch_shipyard_tpu.ops.quantization import quantize_int8_rows
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(batch, 1, heads, depth), jnp.float32)
    k_f = jnp.asarray(rng.randn(batch, t_len, heads, depth),
                      jnp.float32)
    v_f = jnp.asarray(rng.randn(batch, t_len, heads, depth),
                      jnp.float32)
    ck, ks = quantize_int8_rows(k_f)
    cv, vs = quantize_int8_rows(v_f)
    return q, k_f, v_f, ck, ks, cv, vs


def test_dense_decode_int8_kernel_matches_dequant_einsum():
    """The in-kernel int8 dequant dense decode kernel
    (ops/decode_attention.py, interpret mode) vs the existing
    dequantize + einsum path, over ragged lengths INCLUDING the
    short-prefix masked region (length 1 and lengths straddling the
    kernel's block boundary)."""
    from batch_shipyard_tpu.ops import decode_attention as dd
    q, _, _, ck, ks, cv, vs = _int8_cache()
    lengths = jnp.asarray([1, 3, 16, 17, 63, 64], jnp.int32)
    got = dd.dense_decode_attention_kernel(q, ck, cv, ks, vs,
                                           lengths, interpret=True)
    want = dd.dense_decode_attention_xla(q, ck, cv, ks, vs, lengths)
    rel = (np.linalg.norm(np.asarray(got - want)) /
           np.linalg.norm(np.asarray(want)))
    assert rel < 1e-5, rel
    # And both within quantization noise of the fp cache.
    q2, k_f, v_f, *_ = _int8_cache()
    ones = jnp.ones(k_f.shape[:3], jnp.float32)
    ref = dd.dense_decode_attention_xla(q2, k_f, v_f, ones, ones,
                                        lengths)
    rel_fp = (np.linalg.norm(np.asarray(want - ref)) /
              np.linalg.norm(np.asarray(ref)))
    assert rel_fp < 0.02, rel_fp


def test_dense_decode_impl_resolution(monkeypatch):
    """auto resolves from the backend alone (kernel on TPU, xla
    elsewhere); explicit impls pass through; unknown impls fail
    fast."""
    from batch_shipyard_tpu.ops import decode_attention as dd
    assert dd.resolve_dense_decode_impl("kernel") == "kernel"
    assert dd.resolve_dense_decode_impl("xla") == "xla"
    with pytest.raises(ValueError):
        dd.resolve_dense_decode_impl("bogus")
    assert dd.resolve_dense_decode_impl(None) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dd.resolve_dense_decode_impl(None) == "kernel"


def test_dense_decode_kernel_through_transformer():
    """The flax dense int8 decode path with decode_attention_impl=
    'kernel' (interpret mode) matches the einsum path end to end —
    prefill via the multi-token insert, then one kernel decode
    step."""
    import dataclasses
    from jax.experimental.pallas import tpu as pltpu
    from batch_shipyard_tpu.models import inference as inf
    from batch_shipyard_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(
        vocab_size=97, d_model=64, n_layers=2, n_heads=4, d_head=16,
        d_ff=128, max_seq_len=64, dtype=jnp.float32,
        param_dtype=jnp.float32)
    params = tfm.TransformerLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    prompt = jnp.asarray([[5, 17, 31, 2, 9, 40]], jnp.int32)

    def decode_logits(impl):
        dcfg = dataclasses.replace(
            inf.decode_config(cfg, 64), kv_cache_dtype="int8",
            decode_attention_impl=impl)
        model = tfm.TransformerLM(dcfg)
        cache = inf.empty_cache(model, 1)
        _, mutated = model.apply(
            {"params": params, "cache": cache}, prompt,
            return_hidden=True, mutable=["cache"])
        logits, _ = model.apply(
            {"params": params, "cache": mutated["cache"]},
            jnp.asarray([[7]], jnp.int32),
            positions=jnp.asarray([[6]], jnp.int32),
            mutable=["cache"])
        return logits

    ref = decode_logits("xla")
    with pltpu.force_tpu_interpret_mode():
        got = decode_logits("kernel")
    rel = (np.linalg.norm(np.asarray(got - ref)) /
           np.linalg.norm(np.asarray(ref)))
    assert rel < 1e-5, rel


@pytest.mark.parametrize("scale", [0.1, 1.0])
@pytest.mark.slow
def test_flash_ring_merge_gradients(scale):
    """Gradients flow correctly through the merge + flash building
    blocks (2-shard simulated ring vs oracle). The merge weights
    w_i = exp(lse_i - m) depend on each block's lse, so this also
    covers the lse-cotangent term of the flash backward; unit-scale
    inputs + a relative-error assertion keep atol from masking a
    missing term (advisor round-1 finding)."""
    from jax.experimental.pallas import tpu as pltpu
    q, k, v = make_qkv(batch=1, seq=256, heads=2, depth=64)
    q, k, v = q * (scale / 0.1), k * (scale / 0.1), v * (scale / 0.1)

    def ring_sim(q, k, v):
        # The production virtual-shard path: same 3-case rotation +
        # merge code the shard_map ring body runs.
        return ring.ring_attention_virtual_shards(q, k, v, sp=2,
                                                  causal=True)

    def loss_ref(q, k, v):
        return jnp.sum(attn.mha_reference(q, k, v, causal=True) ** 2)

    with pltpu.force_tpu_interpret_mode():
        def loss_sim(q, k, v):
            return jnp.sum(ring_sim(q, k, v) ** 2)
        g_sim = jax.grad(loss_sim, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gg in zip(g_ref, g_sim):
        gr, gg = np.asarray(gr), np.asarray(gg)
        np.testing.assert_allclose(gg, gr, atol=5e-5 * scale ** 2,
                                   rtol=5e-4)
        # Relative error of the whole gradient tensor, so atol on
        # small entries cannot hide a systematically missing term.
        rel = (np.linalg.norm(gg - gr) /
               max(np.linalg.norm(gr), 1e-30))
        assert rel < 1e-4, f"relative grad error {rel:.2e}"


# ---- the band, grouped K/V, and a prefill over a cache in blocks ----

def _band_oracle(q, k, v, start, window):
    """Masked softmax over the whole cache, written out."""
    batch, seq, heads, depth = q.shape
    rows = k.shape[1]
    kv_heads = k.shape[2] // depth
    q_pos = np.asarray(start)[:, None] + np.arange(seq)[None]
    k_pos = np.arange(rows)
    mask = k_pos[None, None, :] <= q_pos[:, :, None]
    if window:
        mask &= k_pos[None, None, :] > q_pos[:, :, None] - window
    scores = attn._grouped_scores(
        q, k.reshape(batch, rows, kv_heads, depth)) / np.sqrt(depth)
    probs = jax.nn.softmax(
        jnp.where(mask[:, None], scores, -1e30), axis=-1)
    return attn._grouped_values(
        probs, v.reshape(batch, rows, kv_heads, depth))


@pytest.mark.parametrize("window", (0, 200))
@pytest.mark.parametrize("start", ([0, 0], [256, 512], [300, 700]))
def test_cached_prefill_attention_reads_what_the_mask_admits(
        start, window, monkeypatch):
    """A segment of 256 queries at positions start .. against a cache
    of 1,024 rows (2 K/V heads under 4 query heads): the XLA loop over
    the band's key blocks and the Pallas kernel (interpret mode,
    blocks of 128) against one masked softmax over the whole cache."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.RandomState(len(start) + window)
    q = jnp.asarray(rng.randn(2, 256, 4, 128), jnp.float32)
    k = jnp.asarray(rng.randn(2, 1024, 256), jnp.float32)
    v = jnp.asarray(rng.randn(2, 1024, 256), jnp.float32)
    start = jnp.asarray(start, jnp.int32)
    want = np.asarray(_band_oracle(q, k, v, start, window))
    got = attn.cached_prefill_attention_xla(q, k, v, start, window,
                                            block_k=128)
    # float32: the order of the sums alone
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-6)
    monkeypatch.setattr(attn, "PREFILL_BLOCK_Q", 128)
    monkeypatch.setattr(attn, "PREFILL_BLOCK_K", 128)
    with pltpu.force_tpu_interpret_mode():
        got = attn.cached_prefill_attention_kernel(q, k, v, start,
                                                   window)
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-6)


def test_a_softmax_kept_in_bfloat16_is_the_lower_precision(monkeypatch):
    """softmax_dtype bfloat16 (a check's control): the XLA loop and
    the kernel round the same terms (scores, the running denominator
    and weighted sum, once a key block), so they agree with each other
    far closer than either does with the float32 softmax, and float32
    is the identity."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.RandomState(11)
    q = jnp.asarray(3 * rng.randn(1, 256, 4, 128), jnp.float32)
    k = jnp.asarray(rng.randn(1, 1024, 256), jnp.float32)
    v = jnp.asarray(rng.randn(1, 1024, 256), jnp.float32)
    start = jnp.asarray([700], jnp.int32)
    x = jnp.asarray([1.00390625, -3.3], jnp.float32)
    assert attn.kept_in(x, jnp.float32) is x
    assert np.asarray(attn.kept_in(x, jnp.bfloat16)).tolist() == [
        1.0, -3.296875]
    monkeypatch.setattr(attn, "PREFILL_BLOCK_Q", 128)
    monkeypatch.setattr(attn, "PREFILL_BLOCK_K", 128)
    sound = np.asarray(attn.cached_prefill_attention_xla(
        q, k, v, start, 300, block_k=128))
    low = np.asarray(attn.cached_prefill_attention_xla(
        q, k, v, start, 300, block_k=128, softmax_dtype=jnp.bfloat16))
    with pltpu.force_tpu_interpret_mode():
        kernel = np.asarray(attn.cached_prefill_attention_kernel(
            q, k, v, start, 300, softmax_dtype=jnp.bfloat16))
    rounding = np.abs(low - sound).max()
    assert rounding > 3e-3
    assert np.abs(kernel - low).max() < rounding / 4


def test_prefill_attention_dispatch(monkeypatch):
    assert attn.prefill_kernel_shapes_ok(4096, 16384, 128)
    assert attn.prefill_kernel_shapes_ok(128, 512, 128)
    assert not attn.prefill_kernel_shapes_ok(32, 256, 16)
    q = jnp.zeros((1, 32, 4, 16))
    cache = jnp.zeros((1, 64, 32))
    with pytest.raises(ValueError, match="impl"):
        attn.cached_prefill_attention(q, cache, cache, 0, impl="no")
    assert attn.cached_prefill_attention(q, cache, cache,
                                         0).shape == q.shape


@pytest.mark.parametrize("window", (0, 10))
def test_blockwise_and_reference_take_a_band_and_grouped_kv(window):
    rng = np.random.RandomState(window)
    q = jnp.asarray(rng.randn(1, 64, 4, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 64, 2, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 64, 2, 16), jnp.float32)
    want = attn.mha_reference(q, jnp.repeat(k, 2, axis=2),
                              jnp.repeat(v, 2, axis=2), True,
                              window=window)
    np.testing.assert_allclose(
        np.asarray(attn.mha_reference(q, k, v, True, window=window)),
        np.asarray(want), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(attn.blockwise_mha(q, k, v, True, block_size=16,
                                      window=window)),
        np.asarray(want), atol=1e-6)
    if window:
        # the band by hand: row 40 sees keys 31 .. 40 alone
        scores = (np.asarray(k[0, 31:41, 0]) @ np.asarray(q[0, 40, 0])
                  / 4.0)
        probs = np.exp(scores - scores.max())
        np.testing.assert_allclose(
            np.asarray(want[0, 40, 0]),
            (probs / probs.sum()) @ np.asarray(v[0, 31:41, 0]),
            atol=1e-6)


# ---- the block-causal mask (a model that generates by diffusion
# ---- over blocks: TransformerConfig.block_diffusion) ----

def _block_oracle(q, k, v, start, block):
    """One masked softmax over the whole cache: key j is visible to
    the query at position i iff j // block <= i // block."""
    batch, seq, heads, depth = q.shape
    rows = k.shape[1]
    kv_heads = k.shape[2] // depth
    q_pos = np.asarray(start)[:, None] + np.arange(seq)[None]
    mask = (np.arange(rows)[None, None, :] // block
            <= q_pos[:, :, None] // block)
    scores = attn._grouped_scores(
        q, k.reshape(batch, rows, kv_heads, depth)) / np.sqrt(depth)
    probs = jax.nn.softmax(
        jnp.where(mask[:, None], scores, -1e30), axis=-1)
    return attn._grouped_values(
        probs, v.reshape(batch, rows, kv_heads, depth))


def test_the_block_causal_mask_by_hand():
    got = np.asarray(attn._causal_mask(jnp.arange(6), jnp.arange(8),
                                       block=4))
    want = np.asarray([[j // 4 <= i // 4 for j in range(8)]
                       for i in range(6)])
    assert (got == want).all()
    assert (np.asarray(attn.block_end(jnp.arange(9), 4))
            == [3, 3, 3, 3, 7, 7, 7, 7, 11]).all()
    assert (np.asarray(attn.block_end(jnp.arange(5), 0))
            == np.arange(5)).all()          # 0: plain causal
    with pytest.raises(ValueError, match="power of two"):
        attn.block_end(jnp.arange(4), 3)


@pytest.mark.parametrize("start", ([0, 0], [256, 512], [4, 700]))
def test_cached_prefill_attention_under_the_block_causal_mask(
        start, monkeypatch):
    """A segment of 256 queries at positions start .. (whole blocks of
    4) against a cache of 1,024 rows: the XLA loop and the Pallas
    kernel (interpret mode, blocks of 128) against one masked softmax
    over the whole cache; a query's block reaches up to three keys
    past its own position, and past the segment's last key block where
    the segment ends inside one."""
    from jax.experimental.pallas import tpu as pltpu
    rng = np.random.RandomState(len(start))
    q = jnp.asarray(rng.randn(2, 256, 4, 128), jnp.float32)
    k = jnp.asarray(rng.randn(2, 1024, 256), jnp.float32)
    v = jnp.asarray(rng.randn(2, 1024, 256), jnp.float32)
    start = jnp.asarray(start, jnp.int32)
    want = np.asarray(_block_oracle(q, k, v, start, 4))
    causal = np.asarray(attn.cached_prefill_attention_xla(
        q, k, v, start, block_k=128))
    assert np.abs(causal - want).max() > 1e-2   # it is another mask
    got = attn.cached_prefill_attention_xla(q, k, v, start, block_k=128,
                                            block=4)
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-6)
    monkeypatch.setattr(attn, "PREFILL_BLOCK_Q", 128)
    monkeypatch.setattr(attn, "PREFILL_BLOCK_K", 128)
    with pltpu.force_tpu_interpret_mode():
        got = attn.cached_prefill_attention_kernel(q, k, v, start,
                                                   block=4)
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-6)


def test_blockwise_and_reference_take_the_block_causal_mask():
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 64, 4, 32), jnp.float32)
    k = jnp.asarray(rng.randn(2, 64, 2, 32), jnp.float32)
    v = jnp.asarray(rng.randn(2, 64, 2, 32), jnp.float32)
    want = np.asarray(_block_oracle(
        q, k.reshape(2, 64, 64), v.reshape(2, 64, 64),
        jnp.zeros((2,), jnp.int32), 8))
    np.testing.assert_allclose(np.asarray(attn.mha_reference(
        q, k, v, block=8)), want, atol=2e-6)
    np.testing.assert_allclose(np.asarray(attn.blockwise_mha(
        q, k, v, block_size=16, block=8)), want, atol=2e-6)

"""Multi-head latent attention below the engine: the mixer's three
paths through the same weights (a forward without a cache, the
expanded insert into a dense cache, the absorbed paged decode call),
the latent paged-decode kernel in interpret mode against its gather,
the flash prefill kernel with values of a depth of their own, the
sandwich norm as a field whose default leaves a block what it was, the
road's table, and the bytes a page id names read off the leaves of
every pool kind.

float32 cases hold to rounding (atol 2e-5 on activations of order 1:
the three paths sum the same products in different orders); the
bfloat16 kernel case holds to 2e-2, one bfloat16 spacing of outputs of
order 1 (the probabilities are rounded to bfloat16 for the value
matmul on both roads, in a different order of accumulation)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from batch_shipyard_tpu.models import inference as inf
from batch_shipyard_tpu.models import transformer as tfm
from batch_shipyard_tpu.ops import attention as attn_ops
from batch_shipyard_tpu.ops import paged_attention as pa

LATENT = tfm.LatentKV(q_rank=32, kv_rank=16, nope_dim=16, rope_dim=8,
                      v_dim=16)
PAGE = 4


def _config(**more):
    return tfm.TransformerConfig(**{**dict(
        vocab_size=97, d_model=64, n_layers=2, n_heads=4, d_head=24,
        d_ff=96, dtype=jnp.float32, param_dtype=jnp.float32,
        block_kinds=("attn", "mlp"), latent=LATENT, sandwich_norm=True,
        tie_embeddings=False, prefill_blocks=True, max_seq_len=64,
        rope_theta=25.6e6), **more})


@pytest.fixture(scope="module")
def stack():
    """(config, full-forward model, params, 14 tokens, their logits
    by the forward without a cache)."""
    cfg = _config()
    model = tfm.TransformerLM(cfg)
    tokens = (jnp.arange(14) * 7 % 97)[None]
    params = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    return cfg, model, params, tokens, jax.jit(
        lambda: model.apply({"params": params}, tokens))()


def _paged(cfg, params, slots=1, pages=9):
    model = tfm.TransformerLM(dataclasses.replace(
        inf.decode_config(cfg, 32), kv_page_size=PAGE,
        kv_num_pages=pages, spec_window=1))
    cache = inf.empty_cache(model, slots)
    table = (jnp.arange(slots)[:, None] * 3 + jnp.arange(
        cache["layer_0"]["attn"]["block_table"].shape[1])[None]) % pages

    def fix(path, leaf):
        return table.astype(jnp.int32) \
            if path[-1].key == "block_table" else leaf
    return model, jax.tree_util.tree_map_with_path(fix, cache)


def _apply(model, params, **static):
    """model.apply over (cache, tokens, positions[, live]) as ONE
    compiled program a shape -> (out, cache)."""
    def call(cache, tokens, positions, live=None):
        out, mut = model.apply(
            {"params": params, "cache": cache}, tokens,
            positions=positions, live=live, mutable=["cache"], **static)
        return out, mut["cache"]
    return jax.jit(call)


def test_the_tree_and_the_cache_are_one_row_a_token(stack):
    cfg, _model, params, _tokens, _full = stack
    attn = params["layer_0"]["attn"]
    assert set(attn) == {"q_down", "q_norm", "q_up", "kv_down",
                         "kv_norm", "kv_up", "o_proj"}
    assert attn["kv_up"].shape == (16, 4 * (16 + 16))
    assert attn["kv_down"]["kernel"].shape == (64, 16 + 8)
    assert set(params["layer_0"]) == {"norm", "attn", "post_norm"}
    assert set(params["layer_1"]) == {"norm", "mlp", "post_norm"}
    # 24 lanes of numbers in whole lane tiles: no V leaf, no head axis
    assert LATENT.row_lanes == 128
    dense = inf.empty_cache(
        tfm.TransformerLM(inf.decode_config(cfg, 32)), 2)
    assert jax.tree_util.tree_map(jnp.shape, dense) == {
        "layer_0": {"attn": {"index": (2,), "kv": (2, 32, 128)}}}
    _model, paged = _paged(cfg, params, slots=2)
    assert set(paged["layer_0"]["attn"]) == {"kv_pages", "block_table",
                                             "length"}
    assert paged["layer_0"]["attn"]["kv_pages"].shape == (9, PAGE, 128)
    assert tfm.LatentKV().row_lanes == 640      # 512 + 64 -> 5 tiles


def test_the_expanded_insert_equals_the_forward_without_a_cache(stack):
    cfg, _model, params, tokens, full = stack
    model = tfm.TransformerLM(inf.decode_config(cfg, 32))
    cache = inf.empty_cache(model, 1)
    out, cache = _apply(model, params, key_reach=16)(
        cache, tokens[:, :9], jnp.arange(9))
    np.testing.assert_allclose(out, full[:, :9], atol=2e-5)
    # ... and a second segment over the first's rows, unbounded
    out, cache = _apply(model, params)(cache, tokens[:, 9:],
                                       jnp.arange(9, 14))
    np.testing.assert_allclose(out, full[:, 9:], atol=2e-5)
    assert int(cache["layer_0"]["attn"]["index"][0]) == 14


@pytest.mark.parametrize("positions", [1, 2])
def test_the_absorbed_decode_equals_the_expanded_form_on_the_same_cache(
        stack, positions):
    """Token by token (or two by two) through the pool: every call's
    rows land through the block table, across page edges (pages of 4),
    and the absorbed scores and sums are the expanded form's."""
    cfg, _model, params, tokens, full = stack
    model, cache = _paged(cfg, params)
    step = _apply(model, params)
    at = 0
    while at < 14:
        n = min(positions, 14 - at)
        out, cache = step(cache, tokens[:, at:at + n],
                          jnp.arange(at, at + n)[None])
        np.testing.assert_allclose(out, full[:, at:at + n], atol=2e-5)
        at += n
    assert int(cache["layer_0"]["attn"]["length"][0]) == 14


def test_the_rotary_key_left_out_is_another_function(stack):
    """The check's structural control (built by the benchmark's model
    module over the served class, which has no switch for it) drops
    the rotary term from the paged call's scores, and a sound model
    served in the same process afterwards runs what it ran."""
    from benchmark.models import latent_moe_mtp
    cfg, _model, params, tokens, full = stack
    off = dataclasses.replace(
        cfg, latent=latent_moe_mtp.decode_rope_left_out(LATENT))
    assert not hasattr(LATENT, "decode_rope")
    outs = []
    for config in (cfg, off):
        model, cache = _paged(config, params)
        step = _apply(model, params)
        for at in range(6):
            out, cache = step(cache, tokens[:, at:at + 1],
                              jnp.array([[at]]))
        outs.append(out)
    np.testing.assert_allclose(outs[0], full[:, 5:6], atol=2e-5)
    assert float(jnp.max(jnp.abs(outs[1] - outs[0]))) > 1e-3


def test_a_parked_slot_reads_zeros_and_disturbs_nobody(stack):
    cfg, _model, params, tokens, full = stack
    model, cache = _paged(cfg, params, slots=3, pages=12)
    live = jnp.array([True, False, True])
    both = jnp.concatenate([tokens] * 3)
    step = _apply(model, params)
    for at in range(5):
        out, cache = step(cache, both[:, at:at + 1],
                          jnp.full((3, 1), at), live)
    np.testing.assert_allclose(out[0], full[0, 4:5], atol=2e-5)
    np.testing.assert_allclose(out[2], full[0, 4:5], atol=2e-5)


def _pool(rng, pages, page, lanes, dtype):
    return jnp.asarray(rng.standard_normal((pages, page, lanes)),
                       dtype)


@pytest.mark.parametrize("seq", [1, 2])
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_the_kernel_equals_the_gather(seq, dtype, atol):
    """Pallas interpret mode: lengths on a page's edge, one key past
    it, a context of several chunks (18 pages of 8 under chunks of 8
    pages), a slot of one position's keys, and parked slots (length 0)
    first, between and last, whose first chunks the hand-over passes
    over."""
    rng = np.random.default_rng(seq)
    batch, heads, lanes, value, page = 7, 4, 256, 128, 8
    pool = _pool(rng, 40, page, lanes, dtype)
    q = jnp.asarray(rng.standard_normal((batch, seq, heads, lanes)),
                    dtype) * 0.3
    table = jnp.asarray(rng.permutation(40)[:batch * 5].reshape(
        batch, 5).repeat(4, axis=1)[:, :18], jnp.int32)
    lengths = jnp.asarray([0, 16, 17, 0, 18 * page, seq, 0], jnp.int32)
    kw = dict(value_lanes=value, scale=0.2)
    want = pa.mla_paged_decode_attention_xla(q, pool, table, lengths,
                                             **kw)
    with pltpu.force_tpu_interpret_mode():
        got = pa.mla_paged_decode_attention_kernel(q, pool, table,
                                                   lengths, **kw)
    assert got.shape == (batch, seq, heads, value) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)
    assert not np.asarray(got, np.float32)[[0, 3, 6]].any()
    # position r sees one key more than position r - 1: its own
    if seq == 2:
        alone = pa.mla_paged_decode_attention_xla(
            q[:, :1], pool, table, jnp.maximum(lengths - 1, 0), **kw)
        np.testing.assert_allclose(
            np.asarray(want[:, :1], np.float32)[[1, 2, 4]],
            np.asarray(alone, np.float32)[[1, 2, 4]], atol=atol)


def test_the_softmax_kept_in_bfloat16_is_another_number():
    rng = np.random.default_rng(3)
    pool = _pool(rng, 40, 8, 256, jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, 1, 4, 256)), jnp.float32)
    table = jnp.arange(36, dtype=jnp.int32).reshape(2, 18)
    lengths = jnp.asarray([140, 99], jnp.int32)
    kw = dict(value_lanes=128, scale=0.2)
    exact = pa.mla_paged_decode_attention_xla(q, pool, table, lengths,
                                              **kw)
    with pltpu.force_tpu_interpret_mode():
        rough = pa.mla_paged_decode_attention_kernel(
            q, pool, table, lengths, softmax_dtype=jnp.bfloat16, **kw)
    gap = float(jnp.max(jnp.abs(rough - exact)))
    assert 1e-4 < gap < 5e-2


@pytest.mark.parametrize("impl,backend,road", [
    (None, "cpu", "mla_xla"), (None, "tpu", "mla_kernel"),
    ("kernel", "cpu", "mla_kernel"), ("xla", "tpu", "mla_xla")])
@pytest.mark.parametrize("positions", [1, 2])
def test_what_the_road_means_for_a_latent_pool(monkeypatch, impl,
                                               backend, road, positions):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert pa.paged_decode_road(impl, grouped=True, latent=True,
                                positions=positions) == road
    for refused in ({"window": 128}, {"int8": True}):
        with pytest.raises(NotImplementedError):
            pa.paged_decode_road(impl, grouped=True, latent=True,
                                 **refused)


@pytest.mark.parametrize("start", [0, 128])
def test_the_prefill_kernel_takes_values_of_their_own_depth(start):
    """q and k 256 lanes deep (192 of numbers, the rest zeros) beside
    values of 128, the scores' factor handed in: the flash prefill
    kernel in interpret mode against the XLA form, and both against a
    plain masked softmax over the 192."""
    rng = np.random.default_rng(5)
    heads, seq, rows = 2, 128, 512

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q, k = draw(1, seq, heads, 192) * 0.2, draw(1, rows, heads, 192)
    v = draw(1, rows, heads, 128)
    fill = [(0, 0)] * 3 + [(0, 64)]
    args = (jnp.pad(q, fill), jnp.pad(k, fill).reshape(1, rows, -1),
            v.reshape(1, rows, -1), jnp.array([start]))
    kw = dict(v_depth=128, scale=192 ** -0.5)
    xla = attn_ops.cached_prefill_attention_xla(*args, **kw)
    kernel = attn_ops.cached_prefill_attention_kernel(
        *args, interpret=True, **kw)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 192 ** -0.5
    visible = jnp.arange(rows)[None, :] <= start + jnp.arange(
        seq)[:, None]
    plain = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
        jnp.where(visible, scores, -jnp.inf), axis=-1), v)
    assert xla.shape == kernel.shape == (1, seq, heads, 128)
    np.testing.assert_allclose(xla, plain, atol=2e-5)
    np.testing.assert_allclose(kernel, plain, atol=2e-5)


def test_the_key_depth_is_padded_where_the_kernel_takes_the_call(
        monkeypatch):
    assert attn_ops.prefill_key_depth(192, 2048, 8192) == 192  # no TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attn_ops.prefill_key_depth(192, 2048, 8192) == 256
    assert attn_ops.prefill_key_depth(24, 16, 64) == 24   # no tiling


# ------------------------------------------------------ sandwich norms


def test_the_sandwich_field_off_is_todays_block_bit_for_bit():
    """x + Mixer(norm(x)) computed by hand from the block's own
    submodules is what a MixerBlock gives with the field off, to the
    bit, and its tree has no post_norm; with the field on the mixer's
    output passes the new norm before it is added."""
    cfg = tfm.TransformerConfig(
        vocab_size=97, d_model=32, n_layers=1, n_heads=2, d_head=16,
        d_ff=64, dtype=jnp.float32, param_dtype=jnp.float32,
        block_kinds=("mlp",))
    assert cfg.sandwich_norm is False and cfg.latent is None
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 32))
    block = tfm.MixerBlock(cfg, "mlp")
    params = block.init(jax.random.PRNGKey(2), x, None)["params"]
    assert set(params) == {"norm", "mlp"}
    got, normed = block.apply({"params": params}, x, None)
    by_hand = tfm.RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype).apply(
        {"params": params["norm"]}, x)
    want = x + tfm.MLP(cfg).apply({"params": params["mlp"]}, by_hand)
    assert np.array_equal(got, want) and np.array_equal(normed, by_hand)

    on = tfm.MixerBlock(dataclasses.replace(cfg, sandwich_norm=True),
                        "mlp")
    scale = jnp.linspace(0.5, 2.0, 32)
    sandwiched, _ = on.apply(
        {"params": {**params, "post_norm": {"scale": scale}}}, x, None)
    out = tfm.MLP(cfg).apply({"params": params["mlp"]}, by_hand)
    want = x + tfm.RMSNorm(eps=cfg.norm_eps, dtype=cfg.dtype).apply(
        {"params": {"scale": scale}}, out)
    np.testing.assert_allclose(sandwiched, want, atol=1e-6)


def test_the_module_is_sandwiched_with_the_stack():
    cfg = _config(mtp_modules=1)
    model = tfm.TransformerLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    for layer in ("layer_0", "layer_1"):
        assert {"norm", "post_norm"} <= set(params["mtp"][layer])
    assert "kv_up" in params["mtp"]["layer_0"]["attn"]


# ----------------------------------------- the bytes a page id names


def _pool_kinds():
    base = dict(vocab_size=97, d_model=64, n_layers=2, n_heads=4,
                d_head=16, d_ff=96, dtype=jnp.bfloat16,
                param_dtype=jnp.bfloat16)
    return {
        # K and V rows of H * D, two layers
        "mha": (tfm.TransformerConfig(**base), 2 * 2 * 64 * 2),
        # ... of Hkv * D
        "grouped": (tfm.TransformerConfig(**base, n_kv_heads=2),
                    2 * 2 * 32 * 2),
        # a window layer's ring is of no page: the full layer's alone
        "ring+pool": (tfm.TransformerConfig(
            **base, n_kv_heads=2, block_kinds=("attn", "attn"),
            layer_windows=(8, 0)), 2 * 32 * 2),
        # int8 rows and a float32 scale a head
        "int8": (tfm.TransformerConfig(**base, kv_cache_dtype="int8"),
                 2 * 2 * (64 + 4 * 4)),
        # ONE row of row_lanes a layer: an attn layer and the module's
        "latent": (_config(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                           mtp_modules=1), 2 * 128 * 2),
    }


@pytest.mark.parametrize("kind", list(_pool_kinds()))
def test_the_reckoned_bytes_are_the_leaves(kind):
    """inference.pool_page_bytes, the pool's stats and the engine's
    prefix_stats read ONE number: the pooled leaves' bytes over their
    pages, whatever kind of attention wrote them (no Hkv * D * 2)."""
    from batch_shipyard_tpu.models import serving
    config, row_bytes = _pool_kinds()[kind]
    # (the books are read, nothing is served: shapes for weights)
    params = jax.eval_shape(
        lambda: tfm.TransformerLM(config).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))[
                "params"]
    engine = serving.ContinuousBatcher(
        config, params, num_slots=2, max_decode_len=32, kv_page_size=8,
        kv_num_pages=6)
    leaves = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(
        engine.cache)[0] if path[-1].key in inf.POOL_LEAVES]
    pages = engine.pages.num_pages + 1          # and the scratch page
    assert all(leaf.shape[0] == pages for leaf in leaves)
    assert inf.pool_page_bytes(engine.cache) * pages == sum(
        leaf.nbytes for leaf in leaves)
    stats = engine.prefix_stats()
    assert stats["page_bytes"] == engine.pages.page_bytes \
        == inf.pool_page_bytes(engine.cache) == 8 * row_bytes
    assert stats["bytes_per_token"] == row_bytes

"""Every Pallas kernel in ops/, lowered FOR THE TPU on this CPU box.

Two layers, both without a chip:

  * ``jax.export`` with ``platforms=['tpu']`` runs Pallas's TPU lowering
    — where the block-shape class of refusal lives ("the last two
    dimensions of your block shape [must be] divisible by 8 and 128 …
    or equal to the respective dimensions of the overall array"). The
    paged and dense-int8 decode kernels failed exactly here until their
    blocks took all heads of a page.
  * an ahead-of-time compile against a v5e topology description, which
    runs libtpu's real compiler — Mosaic included — on the kernel:
    VMEM limits, unsupported relayouts, tiling. Needs no device, only
    the installed libtpu; skipped (visibly) where libtpu cannot
    describe a topology.

Shapes: the ones chip_smoke.py serves and trains at, and the ones
tools/tpu_checks.py checks numerics at; forward and backward where
there is one. What neither layer can say is whether the kernel's
NUMBERS are right on the chip — that is tools/tpu_checks.py there.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax import export

from batch_shipyard_tpu.ops import attention as attn
from batch_shipyard_tpu.ops import chunked_loss as cl
from batch_shipyard_tpu.ops import decode_attention as dd
from batch_shipyard_tpu.ops import fused_norm as fn
from batch_shipyard_tpu.ops import grouped_matmul as gm
from batch_shipyard_tpu.ops import paged_attention as pa
from batch_shipyard_tpu.ops import quantization as qz
from batch_shipyard_tpu.ops import ring_collectives as rc

bf16, f32, i8, i32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


def _sq_sum(fn_):
    return lambda *a: jnp.sum(fn_(*a).astype(f32) ** 2)


def _flash(causal):
    return lambda q, k, v: attn.flash_attention(q, k, v, causal)


def _paged(q, k, v, table, lengths, ks=None, vs=None):
    """The paged decode call as the dispatch makes it on a TPU: the
    one-program-a-slot kernel for bf16/f32 pages, the (slot, table
    entry) grid kernel for an int8 MHA pool."""
    return pa.paged_decode_attention(q, k, v, table, lengths,
                                     impl="kernel", k_scales=ks,
                                     v_scales=vs)


def _xent(h, e, t):
    return cl.chunked_softmax_xent(h, e, t, impl="pallas")


def _fused(x, s, w):
    return fn.rmsnorm_matmul(x, s, w, impl="pallas")


def _paged_args(dtype, heads, page, int8=False, batch=8, depth=64,
                max_blocks=8, pages=None, positions=1):
    pages = pages or batch * max_blocks + 1
    # The pool as models/transformer.py stores it: heads folded into
    # the rows, the layout the kernel blocks.
    pool = ((pages, page, heads * depth), i8 if int8 else dtype)
    args = [((batch, positions, heads, depth), dtype), pool, pool,
            ((batch, max_blocks), i32), ((batch,), i32)]
    if int8:
        args += [((pages, page, heads), f32)] * 2
    return args


def _dense_args(dtype, heads, t_len=512, batch=8, depth=64):
    cache = ((batch, t_len, heads, depth), i8)
    scales = ((batch, t_len, heads), f32)
    return [((batch, 1, heads, depth), dtype), cache, cache, scales,
            scales, ((batch,), i32)]


def _qkv(shape, dtype):
    return [(shape, dtype)] * 3


def _gqa_paged(window, softmax_dtype=f32, block=0):
    return lambda q, k, v, table, lengths, *live: \
        pa.gqa_paged_decode_attention_kernel(
            q, k, v, table, lengths, window=window,
            softmax_dtype=softmax_dtype, block=block,
            live_positions=live[0] if live else None)


def _gqa_args(pages, entries, dtype=bf16, batch=48, heads=28,
              kv_heads=4, depth=128, page=64, positions=1):
    pool = ((pages, page, kv_heads * depth), dtype)
    return [((batch, positions, heads, depth), dtype), pool, pool,
            ((batch, entries), i32), ((batch,), i32)]


def _flash_prefill(window, softmax_dtype=f32, block=0):
    return lambda q, k, v, start: attn.cached_prefill_attention_kernel(
        q, k, v, start, window, softmax_dtype=softmax_dtype,
        block=block)


def _mla_paged(softmax_dtype=f32):
    return lambda q, pool, table, lengths: \
        pa.mla_paged_decode_attention_kernel(
            q, pool, table, lengths, value_lanes=512,
            scale=192 ** -0.5, softmax_dtype=softmax_dtype)


def _latent_prefill(q, k, v, start):
    return attn.cached_prefill_attention_kernel(
        q, k, v, start, v_depth=128, scale=192 ** -0.5)


def _prefill_args(seq, rows=16384, heads=28, kv_heads=4, depth=128):
    cache = ((1, rows, kv_heads * depth), bf16)
    return [((1, seq, heads, depth), bf16), cache, cache, ((1,), i32)]


# (id, fn, [(shape, dtype), ...]). "smoke" = chip_smoke.py's shapes
# (bf16, 16 heads x 64, page 64, T 2048, d_model 1024, vocab 32000);
# "checks" = tools/tpu_checks.py's.
CASES = [
    ("flash_fwd_smoke", _flash(True), _qkv((8, 2048, 16, 64), bf16)),
    ("flash_bwd_smoke", jax.grad(_sq_sum(_flash(True)), (0, 1, 2)),
     _qkv((8, 2048, 16, 64), bf16)),
    ("flash_fwd_checks", _flash(True), _qkv((2, 1024, 4, 64), f32)),
    ("flash_bwd_checks", jax.grad(_sq_sum(_flash(True)), (0, 1, 2)),
     _qkv((2, 1024, 4, 64), f32)),
    ("flash_fwd_noncausal_T128", _flash(False),
     _qkv((2, 128, 4, 64), f32)),
    ("flash_bwd_noncausal_T128",
     jax.grad(_sq_sum(_flash(False)), (0, 1, 2)),
     _qkv((2, 128, 4, 64), f32)),
    ("paged_smoke", _paged, _paged_args(bf16, 16, 64)),
    ("paged_int8_smoke", _paged, _paged_args(bf16, 16, 64, int8=True)),
    ("paged_checks", _paged, _paged_args(f32, 4, 16)),
    ("paged_int8_checks", _paged, _paged_args(f32, 4, 16, int8=True)),
    # an MHA pool at Baichuan's served shape (48 slots, 32 heads of
    # 128, 193 pages of 64, a table of 32: 4,096 channels, so 2 pages
    # a chunk), one token a slot and a verify block of two
    ("paged_baichuan", _paged,
     _paged_args(bf16, 32, 64, batch=48, depth=128, max_blocks=32,
                 pages=193)),
    ("paged_baichuan_verify", _paged,
     _paged_args(bf16, 32, 64, batch=48, depth=128, max_blocks=32,
                 pages=193, positions=2)),
    ("dense_int8_smoke", dd.dense_decode_attention_kernel,
     _dense_args(bf16, 16)),
    ("dense_int8_checks", dd.dense_decode_attention_kernel,
     _dense_args(f32, 4)),
    ("fused_norm_smoke", _fused,
     [((16384, 1024), bf16), ((1024,), f32), ((1024, 3072), bf16)]),
    ("fused_norm_checks", _fused,
     [((512, 1024), f32), ((1024,), f32), ((1024, 1536), f32)]),
    ("int8_matmul_smoke", qz.quantized_linear,
     [((16384, 1024), bf16), ((1024, 2816), bf16)]),
    ("int8_matmul_checks", qz.quantized_linear,
     [((256, 512), f32), ((512, 384), f32)]),
    ("xent_fwd_smoke", _xent,
     [((8, 2048, 1024), bf16), ((32000, 1024), f32),
      ((8, 2048), i32)]),
    ("xent_bwd_smoke", jax.grad(_xent, (0, 1)),
     [((8, 2048, 1024), bf16), ((32000, 1024), f32),
      ((8, 2048), i32)]),
    ("xent_fwd_checks", _xent,
     [((2, 256, 128), f32), ((1024, 128), f32), ((2, 256), i32)]),
    ("xent_bwd_checks", jax.grad(_xent, (0, 1)),
     [((2, 256, 128), f32), ((1024, 128), f32), ((2, 256), i32)]),
    # the routed experts' grouped road at both hybrid configurations'
    # widths and the 1,024 bucket's (row, choice) pairs: [pairs, k]
    # rows against [held, k, n] stacks (up, down)
    ("grouped_matmul_nemotron_up", gm.grouped_matmul,
     [((6144, 2688), bf16), ((64, 2688, 1856), bf16), ((64,), i32)]),
    ("grouped_matmul_nemotron_down", gm.grouped_matmul,
     [((6144, 1856), bf16), ((64, 1856, 2688), bf16), ((64,), i32)]),
    ("grouped_matmul_solar_up", gm.grouped_matmul,
     [((8192, 4096), bf16), ((40, 4096, 1280), bf16), ((40,), i32)]),
    ("grouped_matmul_solar_down", gm.grouped_matmul,
     [((8192, 1280), bf16), ((40, 1280, 4096), bf16), ((40,), i32)]),
    # the grouped, windowed paged-decode kernel at the window
    # configuration's published shapes (28 query over 4 K/V heads of
    # 128, 48 slots, pages of 64): a full layer's pool of 6,144 pages
    # behind a 256-entry table, and a window layer's ring of 65 pages
    # a slot under a window of 4,096
    ("gqa_paged_full", _gqa_paged(0), _gqa_args(6145, 256)),
    ("gqa_paged_ring", _gqa_paged(4096), _gqa_args(48 * 65, 65)),
    ("gqa_paged_checks", _gqa_paged(20),
     _gqa_args(64, 12, dtype=f32, batch=4, heads=14, kv_heads=2,
               depth=64, page=8)),
    # the blockwise prefill over a cache: a segment of 4,096 queries
    # against 16,384 rows, full and under the window
    ("flash_prefill_full", _flash_prefill(0), _prefill_args(4096)),
    ("flash_prefill_window", _flash_prefill(4096), _prefill_args(4096)),
    ("flash_prefill_short", _flash_prefill(4096), _prefill_args(512)),
    # ... and both with their softmax kept in bfloat16, as the window
    # configuration's lower-precision control runs them on the chip
    ("gqa_paged_ring_bf16_softmax", _gqa_paged(4096, bf16),
     _gqa_args(48 * 65, 65)),
    ("flash_prefill_window_bf16_softmax", _flash_prefill(4096, bf16),
     _prefill_args(4096)),
    # the block-diffusion configuration's published shapes (32 query
    # over 4 K/V heads of 128, 96 slots, a pool of 4,608 pages behind a
    # 129-entry table): a block of 4 positions whose queries all see
    # all keys, and its prefill under the block-causal mask
    ("gqa_paged_block_all_keys", _gqa_paged(0, block=4),
     _gqa_args(4609, 129, batch=96, heads=32, positions=4)),
    ("gqa_paged_block_all_keys_bf16_softmax",
     _gqa_paged(0, bf16, block=4),
     _gqa_args(4609, 129, batch=96, heads=32, positions=4)),
    # ... and the block pass's call since PR 49: two blocks a slot,
    # block-causal between them, the slot's live positions beside its
    # length (a dead second block is passed over)
    ("gqa_paged_two_blocks", _gqa_paged(0, block=4),
     _gqa_args(4609, 129, batch=96, heads=32, positions=8)
     + [((96,), i32)]),
    ("gqa_paged_two_blocks_bf16_softmax", _gqa_paged(0, bf16, block=4),
     _gqa_args(4609, 129, batch=96, heads=32, positions=8)
     + [((96,), i32)]),
    # (the cell's control that keeps the mask causal inside a block)
    ("gqa_paged_two_halves_causal", _gqa_paged(0),
     _gqa_args(4609, 129, batch=96, heads=32, positions=8)
     + [((96,), i32)]),
    ("flash_prefill_block_causal", _flash_prefill(0, block=4),
     _prefill_args(2048, rows=8192, heads=32)),
    # the multi-token-prediction configuration's published shapes (64
    # query over 8 K/V heads of 128, 96 slots, two query positions a
    # slot): a full layer's pool of 4,608 pages behind a 128-entry
    # table, and a window layer's ring of 4 pages a slot under a
    # window of 128 (a slot's whole visit is one chunk: the hand-over
    # between programs is most of such a call)
    ("gqa_paged_verify_full", _gqa_paged(0),
     _gqa_args(4609, 128, batch=96, heads=64, kv_heads=8, positions=2)),
    ("gqa_paged_verify_ring", _gqa_paged(128),
     _gqa_args(96 * 4, 4, batch=96, heads=64, kv_heads=8, positions=2)),
    # the latent configuration's published shapes (128 heads, a row of
    # 512 + 64 lanes stored as 640, 128 slots, two query positions a
    # slot: 256 query rows against each page read once): a pool of
    # 9,216 pages behind a 193-entry table; and its prefill, a
    # segment of 2,048 queries 256 lanes deep (192 of numbers) against
    # the 8,192 bucket's expanded keys beside values of 128
    ("mla_paged_verify", _mla_paged(),
     [((128, 2, 128, 640), bf16), ((9217, 64, 640), bf16),
      ((128, 193), i32), ((128,), i32)]),
    ("mla_paged_verify_bf16_softmax", _mla_paged(bf16),
     [((128, 2, 128, 640), bf16), ((9217, 64, 640), bf16),
      ((128, 193), i32), ((128,), i32)]),
    ("flash_prefill_latent", _latent_prefill,
     [((1, 2048, 128, 256), bf16), ((1, 8192, 128 * 256), bf16),
      ((1, 8192, 128 * 128), bf16), ((1,), i32)]),
    ("ring_all_gather_virtual", rc.ring_all_gather_virtual,
     [((4, 128, 128), f32)]),
    ("ring_reduce_scatter_virtual", rc.ring_reduce_scatter_virtual,
     [((4, 512, 128), f32)]),
]
_IDS = [case[0] for case in CASES]


@pytest.mark.parametrize("name,fn_,args", CASES, ids=_IDS)
def test_pallas_tpu_lowering(name, fn_, args):
    """Pallas's own TPU lowering accepts the kernel (block shapes,
    primitives with a TPU rule) — and the result really is a Mosaic
    call."""
    abstract = [jax.ShapeDtypeStruct(shape, dtype)
                for shape, dtype in args]
    exported = export.export(jax.jit(fn_), platforms=["tpu"])(*abstract)
    assert "tpu_custom_call" in exported.mlir_module()


@pytest.fixture(scope="module")
def v5e_devices():
    """Compile-only v5e devices from libtpu's topology description —
    no chip is opened."""
    from jax.experimental import topologies
    settings = {"TPU_ACCELERATOR_TYPE": "v5litepod-4",
                "TPU_WORKER_HOSTNAMES": "localhost",
                "TPU_SKIP_MDS_QUERY": "1"}
    saved = {key: os.environ.get(key) for key in settings}
    os.environ.update({k: v for k, v in settings.items()
                       if saved[k] is None})
    try:
        topology = topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu")
    except Exception as exc:  # noqa: BLE001 - libtpu/env dependent
        pytest.skip(f"libtpu cannot describe a v5e topology here: "
                    f"{exc}")
    finally:
        for key, value in saved.items():
            if value is None:
                os.environ.pop(key, None)
    return topology.devices


def _aot_compile(fn_, *abstract):
    """Compile for the (compile-only) devices the abstract arguments'
    shardings name."""
    return jax.jit(fn_).trace(*abstract).lower(
        lowering_platforms=("tpu",)).compile()


def _compile_for(devices, fn_, args):
    sharding = jax.sharding.SingleDeviceSharding(devices[0])
    return _aot_compile(fn_, *[
        jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
        for shape, dtype in args])


@pytest.mark.parametrize("name,fn_,args", CASES, ids=_IDS)
def test_mosaic_compiles_for_v5e(v5e_devices, name, fn_, args):
    """libtpu's compiler (Mosaic) takes the kernel for a v5e."""
    compiled = _compile_for(v5e_devices, fn_, args)
    assert "tpu_custom_call" in compiled.as_text()


_GROUPED = [case for case in CASES
            if case[0].startswith("grouped_matmul_")]


@pytest.mark.parametrize("name,fn_,args", _GROUPED,
                         ids=[case[0] for case in _GROUPED])
def test_the_grouped_matmul_reads_a_stack_where_it_lies(
        v5e_devices, name, fn_, args):
    """No call copies, transposes or pads an expert stack: the device
    keeps [64, 2688, 1856] with 2688 along the lanes, and a kernel
    handed the logical shape instead of the stored view
    (grouped_matmul._stored_k_minor) has all 638 MB of it copied into
    the default layout first. The call's temporaries stay far under
    one stack."""
    stack = args[1][0]
    memory = _compile_for(v5e_devices, fn_, args).memory_analysis()
    assert memory.temp_size_in_bytes < stack[0] * stack[1] * stack[2]


def test_mosaic_refusal_is_visible(v5e_devices):
    """The compile-only path really runs Mosaic: a block that cannot
    fit VMEM is refused, not waved through."""
    from jax.experimental import pallas as pl

    def too_big(x):
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

    with pytest.raises(Exception, match="(?i)vmem"):
        _compile_for(v5e_devices, too_big, [((4096, 4096), f32)])


def test_remote_dma_ring_compiles_on_four_chips(v5e_devices):
    """The multi-chip pallas_dma tier — ring all-gather /
    reduce-scatter and the ring-attention KV permute, forward and
    backward — compiles for the four-chip host. (Its remote DMAs have
    no interpreter inside shard_map on CPU, so this is the only
    tier-1 coverage they get.)"""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from batch_shipyard_tpu.ops import ring_attention as ra
    from batch_shipyard_tpu.parallel import mesh as mesh_mod

    ring = len(v5e_devices)
    mesh = mesh_mod.make_mesh(
        mesh_mod.auto_axis_sizes(ring, sp=ring), devices=v5e_devices)

    x = jax.ShapeDtypeStruct((ring * 128, 128), f32,
                             sharding=NamedSharding(mesh, P("sp")))
    _aot_compile(lambda x: rc.ring_all_gather(x, mesh, "sp"), x)
    y = jax.ShapeDtypeStruct(
        (ring, ring * 128, 128), f32,
        sharding=NamedSharding(mesh, P("sp", None)))
    _aot_compile(lambda y: rc.ring_reduce_scatter(y, mesh, "sp"), y)
    q = jax.ShapeDtypeStruct(
        (2, ring * 1024, 4, 64), bf16,
        sharding=NamedSharding(mesh, P(("dp", "fsdp"), "sp", "tp",
                                       None)))

    def loss(q, k, v):
        return jnp.sum(ra.ring_attention(
            q, k, v, mesh, impl="pallas_dma").astype(f32) ** 2)

    text = _aot_compile(jax.grad(loss, (0, 1, 2)), q, q, q).as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("axes", [{}, {"fsdp": 2, "tp": 2}],
                         ids=["dp4", "fsdp2_tp2"])
def test_train_kernels_partition_on_four_chips(v5e_devices,
                                               monkeypatch, axes):
    """Inside a global-view jit XLA refuses to partition a Mosaic call
    ("Mosaic kernels cannot be automatically partitioned"): the
    attention and the chunked loss the trainer builds for a
    multi-device mesh must carry their own shard_map. Compiles their
    forward+backward for the four-chip host the way the train step
    shards them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from batch_shipyard_tpu.parallel import mesh as mesh_mod
    from batch_shipyard_tpu.parallel import train as train_mod

    # Dispatch as on the chip: flash attention, Pallas loss.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = mesh_mod.make_mesh(
        mesh_mod.auto_axis_sizes(len(v5e_devices), **axes),
        devices=v5e_devices)
    config = train_mod.make_transformer_config(mesh, n_heads=16,
                                               d_head=64)
    lm_loss = train_mod.sharded_lm_loss(mesh)

    def objective(q, k, v, hidden, embedding, targets):
        attended = config.attention_fn(q, k, v, causal=True)
        return (jnp.sum(attended.astype(f32) ** 2) +
                lm_loss(hidden, embedding, targets))

    def on(spec, shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))

    batch = P(("dp", "fsdp"))
    qkv = on(P(("dp", "fsdp"), None, "tp", None), (8, 2048, 16, 64),
             bf16)
    text = _aot_compile(
        jax.grad(objective, (0, 1, 2, 3, 4)), qkv, qkv, qkv,
        on(batch, (8, 2048, 1024), bf16), on(P(), (32000, 1024), f32),
        on(batch, (8, 2048), i32)).as_text()
    # Partitioned, not gathered: the flash call sees one device's
    # share of batch x heads (8*16/4 = 32 rows), never all 128.
    assert "bf16[32,2048,64]" in text
    assert "bf16[128,2048,64]" not in text


@pytest.mark.parametrize("program", ["decode", "prefill", "shared"])
def test_serving_steps_update_the_pool_in_place_on_v5e(v5e_devices,
                                                       program):
    """The serving step programs at the benchmark configuration's
    widths and pool (Baichuan-7B: 32 heads of 128, FFN 11008, 48
    slots, 192 pages of 64 tokens; two layers, so that it compiles in
    seconds), compiled for the v5e: every leaf of the donated cache
    is aliased input to output, no second pool sits in temp, and no
    operation but the in-place row writes (and the kernel's reads)
    touches a whole pool leaf. The leaves are 101 MB each and there
    are 32 of them at 16 layers: a surviving whole-leaf copy
    (undonated cache) or reshape (a pool stored [P, page, H, D] and
    relaid out into the kernel's [P, page, H*D]) was a third of the
    decode step's device time."""
    import dataclasses
    import re

    from batch_shipyard_tpu.models import inference as inf
    from batch_shipyard_tpu.models import serving
    from batch_shipyard_tpu.models import transformer as tfm

    slots, max_len, page, pages = 48, 2048, 64, 192
    chip = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    config = tfm.TransformerConfig(
        vocab_size=64000, d_model=4096, n_layers=2, n_heads=32,
        d_head=128, d_ff=11008, max_seq_len=max_len, dtype=bf16,
        param_dtype=bf16)
    dense = tfm.TransformerLM(inf.decode_config(config, max_len))
    paged = tfm.TransformerLM(dataclasses.replace(
        dense.config, kv_page_size=page, kv_num_pages=pages + 1,
        paged_attention_impl="kernel"))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=chip), tree)

    def arg(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = on_chip(jax.eval_shape(
        lambda: tfm.TransformerLM(config).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), i32))["params"]))
    cache = on_chip(jax.eval_shape(
        lambda: inf.init_cache(paged, None, slots)))
    row = arg((max_len // page,))
    if program == "decode":
        lowered = serving._decode_step.lower(
            paged, inf.SamplingConfig(temperature=0.0), params, cache,
            arg((slots, 1)), arg((slots,)), arg((slots,), jnp.bool_),
            arg((2,), jnp.uint32))
    elif program == "prefill":
        lowered = serving._prefill_paged.lower(
            dense, None, page, params, cache, 0, arg((1, 256)), row,
            200)
    else:
        lowered = serving._prefill_paged_shared.lower(
            dense, None, page, params, cache, 0, arg((1, 256)), row,
            row, row, 128, 328)
    compiled = lowered.compile()
    text = compiled.as_text()
    if program == "decode":
        assert "tpu_custom_call" in text
    leaves = jax.tree_util.tree_leaves(cache)
    cache_bytes = sum(
        leaf.size * leaf.dtype.itemsize for leaf in leaves)
    memory = compiled.memory_analysis()
    # (the small integer leaves are padded to whole tiles on the chip)
    assert 0 <= memory.alias_size_in_bytes - cache_bytes < 2 ** 20
    assert memory.temp_size_in_bytes < cache_bytes / 2
    whole_leaf = re.compile(
        r"= bf16\[%d,%d,(?:4096|32,128)\]\S* ([\w-]+)\("
        % (pages + 1, page))
    touching = {}
    for op in whole_leaf.findall(text):
        touching[op] = touching.get(op, 0) + 1
    writes = (touching.get("scatter", 0) +
              touching.get("dynamic-update-slice", 0))
    assert writes >= 4, touching         # K and V of both layers
    # Each in-place write is the root of one fusion; nothing else
    # (copy, copy-start/-done, reshape, transpose, ...) is the size
    # of a leaf.
    assert set(touching) <= {"parameter", "scatter", "fusion",
                             "dynamic-update-slice"}, touching
    assert touching.get("fusion", 0) == writes, touching


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_the_seat_program_compiles_small_for_v5e(v5e_devices,
                                                 temperature):
    """serving._seat_first behind a prefill at the hybrid cells'
    sizes (96 slots, 65,536 float32 logits), compiled for the v5e:
    it holds the logits once more at most and touches nothing else
    of size."""
    from batch_shipyard_tpu.models import inference as inf
    from batch_shipyard_tpu.models import serving

    chip = jax.sharding.SingleDeviceSharding(v5e_devices[0])

    def arg(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    compiled = serving._seat_first.lower(
        inf.SamplingConfig(temperature=temperature, top_k=40),
        arg((65536,), jnp.float32), arg((2,), jnp.uint32),
        arg((96, 1)), arg((96,)), 0, 0).compile()
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 4 * 65536 * 4
    key, tokens, positions, first = compiled.out_info
    assert (key.shape, tokens.shape, positions.shape, first.shape) == (
        (2,), (96, 1), (96,), (1,))


@pytest.mark.parametrize("program", ["decode", "prefill",
                                     "prefill_grouped"])
def test_a_hybrid_stack_keeps_state_and_pool_in_place_on_v5e(
        v5e_devices, program, monkeypatch):
    """The step programs of a stack of block kinds (one state-space,
    one routed-expert and one attention block at the benchmark's
    second configuration's widths: 64 state-space heads of 64 over a
    state of 128, 32 query over 2 K/V heads of 128, 64 held experts of
    1856 out of a router of 128; 96 slots, 2,400 pages of 64 tokens),
    compiled for the v5e: every leaf of the donated cache, the
    per-slot state included, is aliased input to output; the state
    (201 MB a layer) is advanced where it lies, never copied, relaid
    out or converted whole; and neither are the held experts' weights
    (638 MB a matrix): the two matmuls over all of them read them
    where they lie (the compiler's grouped matmul, the road tried
    first, copied a stack whole for every call: PERF.md, PR 31).
    ``prefill_grouped``: the 1,024 bucket traced as the chip traces it
    (the code that asks jax.default_backend() is told "tpu"), where
    moe.experts_road takes the grouped road: its Pallas kernel
    (ops/grouped_matmul.py) is in the program, and it too reads both
    stacks where they lie, the one the device stores with d_model
    along the lanes ([64, 2688, 1856]: 1856 = 14.5 x 128) through its
    stored view."""
    import dataclasses
    import re

    from batch_shipyard_tpu.models import inference as inf
    from batch_shipyard_tpu.models import moe, serving, ssm
    from batch_shipyard_tpu.models import transformer as tfm

    slots, max_len, page, pages = 96, 2048, 64, 2400
    chip = jax.sharding.SingleDeviceSharding(v5e_devices[0])
    config = tfm.TransformerConfig(
        vocab_size=65536, d_model=2688, n_layers=3, n_heads=32,
        n_kv_heads=2, d_head=128, max_seq_len=max_len, dtype=bf16,
        param_dtype=bf16, use_rope=False, tie_embeddings=False,
        norm_eps=1e-5, block_kinds=("ssm", "experts", "attn"),
        ssm=ssm.SSMConfig(),
        experts=moe.RoutedConfig(
            d_model=2688, n_experts=128, top_k=6, d_expert=1856,
            d_shared=3712, scale=2.5, experts_held=64))
    dense = tfm.TransformerLM(inf.decode_config(config, max_len))
    paged = tfm.TransformerLM(dataclasses.replace(
        dense.config, kv_page_size=page, kv_num_pages=pages + 1))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=chip), tree)

    def arg(shape, dtype=i32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    params = on_chip(jax.eval_shape(
        lambda: tfm.TransformerLM(config).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), i32))["params"]))
    cache = on_chip(jax.eval_shape(
        lambda: inf.init_cache(paged, None, slots)))
    if program == "decode":
        # traced as the chip traces it: the attention block's grouped
        # pool decodes through the grouped kernel (PR 41)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        lowered = serving._decode_step.lower(
            paged, inf.SamplingConfig(temperature=0.0), params, cache,
            arg((slots, 1)), arg((slots,)), arg((slots,), jnp.bool_),
            arg((2,), jnp.uint32))
    else:
        grouped = program == "prefill_grouped"
        if grouped:
            monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        bucket = 1024 if grouped else 512
        assert moe.experts_road(bucket, config.experts) == (
            "grouped" if grouped else "dense")
        row = arg((max_len // page,))
        lowered = serving._prefill_paged.lower(
            dense, None, page, params, cache, 0, arg((1, bucket)), row,
            400)
    compiled = lowered.compile()
    # (without the Mosaic kernels' serialized bodies: three letters
    # turn up in a few hundred KB of base64 by chance, and did)
    text = re.sub(r"[A-Za-z0-9+/=]{200,}", "", compiled.as_text())
    assert ("gmm" in text) == (program == "prefill_grouped")
    assert ("gqa_paged_decode" in text) == (program == "decode")
    moved = re.findall(
        r"= bf16\[64,(?:2688,1856|1856,2688)\]\S* "
        r"(copy|transpose|copy-start|convert)\(", text)
    assert not moved, moved
    cache_bytes = sum(leaf.size * leaf.dtype.itemsize
                      for leaf in jax.tree_util.tree_leaves(cache))
    memory = compiled.memory_analysis()
    assert 0 <= memory.alias_size_in_bytes - cache_bytes < 2 ** 20
    # nothing the size of the state or of the pool sits in temp twice
    assert memory.temp_size_in_bytes < 1.2e9
    state = re.compile(
        r"= f32\[96,(?:64,64|8,8,64),128\]\S* ([\w-]+)\(")
    touching = {}
    for op in state.findall(text):
        touching[op] = touching.get(op, 0) + 1
    # (inside a fusion's body the state meets elementwise operations;
    # what may not appear is anything that MOVES a whole state)
    assert touching and not set(touching) & {
        "copy", "copy-start", "copy-done", "transpose", "reshape",
        "convert", "gather"}, touching


# The TPU lowering (StableHLO) of one decode and one prefill program
# of each configuration the benchmark had before the window
# configuration, at its published sizes, as sha256 prefixes with the
# serialized Mosaic kernels taken out (they carry source locations).
# Recorded on the commit before TransformerConfig gained its per-layer
# windows and rotations, the router's second input, prefill_blocks and
# prefill_chunk, and RoutedConfig its second scoring rule and
# activation: with those at their defaults the three configurations
# lower to the programs they lowered to. A PR that MEANS to change one
# of these programs re-records its line and says so.
# PR 41 re-recorded the two hybrid DECODE lines (c4271df8de746936 and
# 87bf64f7c92c128c until then): paged_attention_impl None now means
# the grouped Pallas kernel on a TPU for their grouped pools, where it
# meant the XLA gather. The four other lines are as PR 40 recorded
# them. (The grouped kernel is jitted inline since then, so that its
# body is traced once for same-shaped call sites: two such sites lower
# to the same text but for the numbering of jax's private helper
# functions, `@_where_<n>`, which is why Nemotron's line, with two
# attention blocks, is not what the plain function gave.)
# PR 43 re-recorded Baichuan's DECODE line alone (f4983349d90ff681
# until then): its MHA pool's bf16 pages decode through the
# one-program-a-slot kernel (sixteen unnamed calls of it, 2 pages a
# chunk) where the (slot, table entry) grid kernel ran. The six other
# lines stand: the grouped pools keep 8 pages a chunk and their name.
# PR 44 re-recorded all four DECODE lines (Baichuan's c6e4739630cf2ed5,
# Nemotron's 61b575fbb23a47df, Solar-Open2's 6624ed0450d5dfa9 and
# SmallThinker's bb97a066f9e4c91b until then): the step's ``active``
# mask reaches every paged decode call as
# select(active, length, 0), one broadcast and one select on a
# [slots] vector before each kernel call (Baichuan's text grows by 32
# lines, two a layer), so that a slot without a request is handed to
# the kernel at length 0. With that select taken out the four programs
# hash to the lines they had: the kernel's own change (its body under
# pl.when(length > 0)) lies in the serialized Mosaic body, which the
# hash leaves out. The three PREFILL lines stand: a prefill runs a
# batch-1 dense cache and no paged call.
# PR 48 re-recorded all four DECODE lines again (Baichuan's
# 33c838760abd7f20, Nemotron's e732c9766bb480d9, Solar-Open2's
# dcf86b2c17ec1406 and SmallThinker's 5b204e50838703f9 until then):
# the one-program-a-slot kernel's wrapper hands the kernel a third
# scalar-prefetch operand, next_seated(lengths) (for each slot the
# next one with a length above 0: whose first chunk its program
# fetches behind its own last), eleven lines on a [slots] vector
# before each kernel call (an iota, a compare, a select, a slice, a
# concatenate and one call of jax's private @cummin, a reduce_window)
# and that helper's ten lines once a program; the custom call takes
# six operands where it took five. With the operand taken out the
# four programs hash to the lines they had: what the kernel does with
# it, and its SMEM scratch, lie in the serialized Mosaic body, which
# the hash leaves out. The three PREFILL lines stand.
ACCEPTED_PROGRAMS = {
    "baichuan-7b-serve-1chip/decode": "1efee61ea6336378",
    "baichuan-7b-serve-1chip/prefill": "d778ca3089991697",
    "nemotron-3-nano-30b-a3b-serve-1chip/decode": "ab26c95c028da4f3",
    "nemotron-3-nano-30b-a3b-serve-1chip/prefill": "9494681929c5e555",
    "solar-open2-250b-serve-1chip/decode": "48f090f68262f1ff",
    "solar-open2-250b-serve-1chip/prefill": "414a9d1036f22da3",
    # new in PR 41 (4cb7c1eba72ff012 at its parent: the same program
    # but for that numbering, nine lines, with eight sites of the
    # grouped kernel; compiled for the v5e the two were instruction
    # for instruction the same, PERF.md section 6)
    "smallthinker-21b-a3b-serve-1chip/decode": "d165891c52a028a6",
}


def _served_programs(config_name, monkeypatch):
    """(module, dims, the program's config, dense model, paged model,
    abstract params, abstract cache, the engine section) of a
    benchmark configuration at its published sizes, traced as the
    chip traces it."""
    import dataclasses

    from batch_shipyard_tpu.models import inference as inf
    from batch_shipyard_tpu.models import transformer as tfm
    from benchmark import spec, weights

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = {key: value for key, value in
             spec.load_config(config_name).items()
             if key != "rehearse_tiny"}
    module = spec.load_model(model)
    dims = module.dims(model)
    engine = model["engine"]
    config = module.program_model(model, dims, engine)
    dense = tfm.TransformerLM(
        inf.decode_config(config, engine["max_decode_len"]))
    paged = tfm.TransformerLM(dataclasses.replace(
        dense.config, kv_page_size=engine["kv_page_size"],
        kv_num_pages=engine["kv_num_pages"] + 1))
    params = weights.abstract_params(module.param_leaves(dims), bf16)
    cache = jax.eval_shape(
        lambda: inf.init_cache(paged, None, engine["num_slots"]))
    return module, dims, config, dense, paged, params, cache, engine


def _lower_step(kind, dense, paged, params, cache, engine, bucket=512,
                chunk=None):
    from batch_shipyard_tpu.models import inference as inf
    from batch_shipyard_tpu.models import serving

    def arg(shape, dtype=i32):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=getattr(
                jax.tree_util.tree_leaves(cache)[0], "sharding", None))

    slots = engine["num_slots"]
    if kind == "decode":
        return serving._decode_step.trace(
            paged, inf.SamplingConfig(temperature=0.0), params, cache,
            arg((slots, 1)), arg((slots,)), arg((slots,), jnp.bool_),
            arg((2,), jnp.uint32)).lower(lowering_platforms=("tpu",))
    return serving._prefill_paged.trace(
        dense, chunk, engine["kv_page_size"], params, cache, 0,
        arg((1, bucket)),
        arg((engine["max_decode_len"] // engine["kv_page_size"],)),
        bucket - 112).lower(lowering_platforms=("tpu",))


def _cut_on_chip(module, dims, config, engine, blocks, chip, **cut):
    """(dense model, paged model, abstract params, abstract cache) of
    ``config`` cut to its first ``blocks`` blocks (``cut``: the other
    per-layer fields, cut alike), every leaf placed on ``chip``."""
    import dataclasses

    from benchmark import weights
    from batch_shipyard_tpu.models import inference as inf
    from batch_shipyard_tpu.models import transformer as tfm

    config = dataclasses.replace(
        config, n_layers=blocks,
        block_kinds=config.block_kinds[:blocks], **cut)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=chip), tree)

    dense = tfm.TransformerLM(
        inf.decode_config(config, engine["max_decode_len"]))
    paged = tfm.TransformerLM(dataclasses.replace(
        dense.config, kv_page_size=engine["kv_page_size"],
        kv_num_pages=engine["kv_num_pages"] + 1))
    leaves = [leaf for leaf in module.param_leaves(dims)
              if not leaf[0][0].startswith("layer_")
              or int(leaf[0][0][6:]) < blocks]
    params = on_chip(weights.abstract_params(leaves, bf16))
    cache = on_chip(jax.eval_shape(
        lambda: inf.init_cache(paged, None, engine["num_slots"])))
    return dense, paged, params, cache


@pytest.mark.parametrize("program", sorted(ACCEPTED_PROGRAMS))
def test_the_accepted_configurations_programs_are_unchanged(
        program, monkeypatch):
    import hashlib
    import re

    config_name, kind = program.split("/")
    _module, _dims, _config, dense, paged, params, cache, engine = \
        _served_programs(config_name, monkeypatch)
    text = _lower_step(kind, dense, paged, params, cache,
                       engine).as_text()
    text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        ACCEPTED_PROGRAMS[program]


def test_the_hybrids_grouped_pools_decode_by_the_grouped_kernel(
        monkeypatch):
    """paged_attention_impl None means the Pallas kernel on a TPU for
    every pool (PR 41), and since PR 43 the same one-program-a-slot
    kernel for every pool of bf16 pages: each hybrid configuration's
    decode program holds one call of it an attention block under its
    own name (Nemotron's cut has two, Solar-Open2's one), Baichuan's
    one a layer WITHOUT a name of its own (the kernel body's: its
    device events keep the scope of attn._decode_attend_paged, which
    paged_decode_roofline's pattern reads), and no other Mosaic
    call."""
    for config_name, grouped, mha in (
            ("nemotron-3-nano-30b-a3b-serve-1chip", 2, 0),
            ("solar-open2-250b-serve-1chip", 1, 0),
            ("baichuan-7b-serve-1chip", 0, 16)):
        _module, _dims, config, dense, paged, params, cache, engine = \
            _served_programs(config_name, monkeypatch)
        assert config.paged_attention_impl is None
        text = _lower_step("decode", dense, paged, params, cache,
                           engine).as_text()
        assert text.count('kernel_name = "gqa_paged_decode"') == grouped
        assert text.count(
            'kernel_name = "_gqa_paged_decode_kernel"') == mha
        assert text.count("stablehlo.custom_call @tpu_custom_call") == \
            grouped + mha


@pytest.mark.parametrize("config_name,sites", [
    ("nemotron-3-nano-30b-a3b-serve-1chip", 2),
    ("baichuan-7b-serve-1chip", 16)])
def test_the_grouped_kernels_body_is_traced_once_a_shape(
        monkeypatch, config_name, sites):
    """Nemotron's decode program has two attention blocks of the same
    shapes, Baichuan's sixteen, and building the engine's cache traces
    the same attention once more: the kernel is jitted inline, so its
    body (about a second of Python on a serving host, which a cell's
    set-up pays) is traced once for all of them, not once a call site
    a program."""
    from batch_shipyard_tpu.models import inference as inf
    from batch_shipyard_tpu.ops import paged_attention as pa

    _module, _dims, _config, dense, paged, params, cache, engine = \
        _served_programs(config_name, monkeypatch)
    traced = []
    body = pa._gqa_paged_decode_kernel
    monkeypatch.setattr(
        pa, "_gqa_paged_decode_kernel",
        lambda *a, **k: traced.append(1) or body(*a, **k))
    jax.clear_caches()
    text = _lower_step("decode", dense, paged, params, cache,
                       engine).as_text()
    assert text.count("stablehlo.custom_call @tpu_custom_call") == sites
    jax.eval_shape(
        lambda: inf.init_cache(paged, None, engine["num_slots"]))
    assert len(traced) == 1
    jax.clear_caches()      # nothing traced through the counter stays


@pytest.mark.parametrize("config_name,blocks", [
    ("nemotron-3-nano-30b-a3b-serve-1chip", 6),
    ("solar-open2-250b-serve-1chip", 4)])
def test_a_hybrids_decode_step_gathers_no_table_on_v5e(
        v5e_devices, config_name, blocks, monkeypatch):
    """Each hybrid configuration's decode step at its published widths
    and engine sizes (96 slots, tables of 32 pages of 64), cut to its
    first ``blocks`` blocks (Nemotron's state-space, experts and
    attention blocks 0-5: 32 query over 2 K/V heads of 128;
    Solar-Open2's attention, experts, delta, experts: 64 over 8) so
    that it compiles in seconds, traced as the chip traces it and
    compiled for the v5e: every leaf of the donated cache is aliased
    input to output; the attention is ONE grouped-kernel call reading
    the pool where it lies; and nothing in the program has the shape
    of a slot's gathered table ([96, 2048, ...] or, before its
    reshape, [96, 32, 64, ...]): the gather of every slot's whole
    table width, its layout copies and its masked softmax over 2,048
    positions (PERF.md section 5: 5.4-5.7 ms of Solar-Open2's 20.3 ms
    step until PR 41) are gone, not moved."""
    import re

    module, dims, config, _dense, _paged, _params, _cache, engine = \
        _served_programs(config_name, monkeypatch)
    assert config.paged_attention_impl is None
    assert (engine["num_slots"], engine["max_decode_len"],
            engine["kv_page_size"]) == (96, 2048, 64)
    assert config.block_kinds[:blocks].count("attn") == 1
    dense, paged, params, cache = _cut_on_chip(
        module, dims, config, engine, blocks,
        jax.sharding.SingleDeviceSharding(v5e_devices[0]))
    compiled = _lower_step("decode", dense, paged, params, cache,
                           engine).compile()
    text = compiled.as_text()
    cache_bytes = sum(leaf.size * leaf.dtype.itemsize
                      for leaf in jax.tree_util.tree_leaves(cache))
    memory = compiled.memory_analysis()
    assert 0 <= memory.alias_size_in_bytes - cache_bytes < 2 ** 20
    assert len(re.findall(r"%gqa_paged_decode\S* = ", text)) == 1
    gathered = re.findall(
        r"= \w+\[96,(?:2048|32,64)[,\]]\S* ([\w-]+)\(", text)
    assert not gathered, gathered
    pool = re.compile(r"= bf16\[2401,64,(?:256|1024)\]\S* ([\w-]+)\(")
    assert set(pool.findall(text)) <= {
        "parameter", "scatter", "fusion", "dynamic-update-slice"}
    # K and V of the one gathered table were 2 x 403 MB in
    # Solar-Open2's step, and twice that with their layout copies
    assert memory.temp_size_in_bytes < 256 * 2 ** 20


@pytest.mark.parametrize("program", ["decode", "prefill_512",
                                     "prefill_16384"])
def test_the_window_stack_keeps_pool_and_rings_in_place_on_v5e(
        v5e_devices, program, monkeypatch):
    """The window configuration's step programs at its published
    widths, pool and rings (28 query over 4 K/V heads of 128, 64 ReGLU
    experts of 768 all held, the whole vocabulary of 151,936; 48 slots,
    6,144 pages and rings of 65 pages of 64 tokens, contexts to
    16,384), cut to its first TWO published layers (one full, one
    window: four blocks) so that it compiles in seconds, compiled for
    the v5e: every leaf of the donated cache is aliased input to
    output, pool and ring alike; the decode step holds one grouped
    kernel an attention layer and touches no whole leaf but by its
    in-place row writes; a prefill holds the blockwise prefill kernel
    (one call a layer; the four segments of the longest bucket are a
    loop) and the grouped matmul, and its temporaries stay at what a
    segment of the window's length holds, far under what a
    [bucket, 16384] score tensor a head would take (28 x 16384 x
    16384 x 4 bytes = 30 GB)."""
    import re

    from batch_shipyard_tpu.models import serving
    from batch_shipyard_tpu.models import transformer as tfm

    module, dims, config, _dense, _paged, _params, _cache, engine = \
        _served_programs("smallthinker-21b-a3b-serve-1chip", monkeypatch)
    assert config.paged_attention_impl == "kernel"
    assert engine["kv_page_size"] == 64
    dense, paged, params, cache = _cut_on_chip(
        module, dims, config, engine, 4,
        jax.sharding.SingleDeviceSharding(v5e_devices[0]),
        layer_windows=config.layer_windows[:4],
        layer_rope=config.layer_rope[:4])
    assert tfm.attention_windows(dense.config) == (0, 4096)
    assert cache["layer_2"]["attn"]["k_ring"].shape == (
        48 * 65, 64, 512)
    if program == "decode":
        lowered = _lower_step("decode", dense, paged, params, cache,
                              engine)
    else:
        lowered = _lower_step(
            "prefill", dense, paged, params, cache, engine,
            bucket=int(program.split("_")[1]),
            chunk=serving.window_segment(dense.config))
    compiled = lowered.compile()
    text = compiled.as_text()
    cache_bytes = sum(leaf.size * leaf.dtype.itemsize
                      for leaf in jax.tree_util.tree_leaves(cache))
    memory = compiled.memory_analysis()
    assert 0 <= memory.alias_size_in_bytes - cache_bytes < 2 ** 20
    whole_leaf = re.compile(
        r"= bf16\[(?:6145|3120),64,512\]\S* ([\w-]+)\(")
    touching = {}
    for op in whole_leaf.findall(text):
        touching[op] = touching.get(op, 0) + 1
    assert set(touching) <= {"parameter", "scatter", "fusion",
                             "dynamic-update-slice"}, touching
    if program == "decode":
        assert len(re.findall(r"%gqa_paged_decode\S* = ", text)) == 2
        assert touching["scatter"] == 4     # K and V of both layers
        assert memory.temp_size_in_bytes < 64 * 2 ** 20
    else:
        assert len(re.findall(r"%flash_prefill_cached\S* = ",
                              text)) == 2
        assert "gmm" in text
        # one call a layer: the segments run as ONE traced forward
        # in a loop; 0.99 GB where the bucket whole holds 2.45
        assert memory.temp_size_in_bytes < 1.2e9

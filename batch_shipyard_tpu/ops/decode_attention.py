"""Pallas dense decode-attention kernel with in-kernel int8 dequant.

The dense int8 KV decode path (models/transformer._decode_attend)
previously dequantized the ENTIRE [B, L, H, D] cache with an
elementwise multiply outside any kernel and bet peak HBM on XLA fusing
it into the attention dots — the paged path (ops/paged_attention.py)
already dequantizes per tile inside its kernel. This kernel closes the
gap for the dense cache: the int8 K/V rows and their per-(position,
head) fp32 scales stream through VMEM tile by tile, the scales are
applied to the tile's scores/probabilities right around the dots, and
HBM holds int8 + scales only — the entire 2x-HBM claim of
kv_cache_dtype='int8' (arxiv 2605.25645 makes that headroom the
serving-throughput lever). tools/tpu_checks.py asserts the claim on
the COMPILED step: no full-cache-sized f32/bf16 buffer in the HLO,
kernel custom-call present (check names dense_decode_int8 /
dense_decode_hlo).

The per-program body is the paged kernel's (decode_block_step: all
heads of one key block, block-diagonal query) — a fix there lands
here too. The grid is (batch, length-blocks): blocks wholly past a
slot's live length are skipped (@pl.when) and their DMAs clamped to
the last live block, exactly the paged kernel's dead-step discipline.

impl=None resolves from the backend alone: the kernel on TPU, the XLA
dequant+einsum formulation (the reference) elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from batch_shipyard_tpu.ops.paged_attention import (
    decode_block_step, decode_scratch_shapes, resolve_kernel_or_xla)

_NEG_INF = -1e30


def _dense_decode_kernel_int8(len_ref, q_ref, k_ref, ks_ref, v_ref,
                              vs_ref, o_ref, *scratch, **static):
    decode_block_step(len_ref[pl.program_id(0)], q_ref, k_ref, ks_ref,
                      v_ref, vs_ref, o_ref, *scratch, **static)


def _largest_block(length: int, preferred: int = 128) -> int:
    """Largest divisor of the cache length <= preferred."""
    block = min(preferred, length)
    while length % block:
        block -= 1
    return block


def dense_decode_attention_kernel(q, cache_k, cache_v, k_scales,
                                  v_scales, lengths,
                                  block: Optional[int] = None,
                                  interpret: bool = False):
    """Pallas path. q: [B, 1, H, D]; cache_k/cache_v: [B, L, H, D]
    int8; k_scales/v_scales: [B, L, H] fp32 per-(position, head)
    absmax scales; lengths: [B] int32 valid-key counts (INCLUDING the
    token written this step — the decode contract never attends an
    unwritten slot). Returns [B, 1, H, D] in q.dtype."""
    batch, seq, heads, depth = q.shape
    assert seq == 1, "dense decode kernel consumes one token per call"
    t_len = cache_k.shape[1]
    block = block or _largest_block(t_len)
    if t_len % block:
        raise ValueError(
            f"cache length {t_len} not divisible by block {block}")
    width = heads * depth

    def tile_index(b, j, ln):
        # Clamp dead steps to the slot's LAST live block: blocks past
        # the length are skipped by @pl.when, so don't spend HBM
        # bandwidth DMA-ing rows nobody reads (the paged kernel's
        # discipline; here every row exists, so this is thrift, not
        # correctness).
        live = jnp.maximum((ln[b] + block - 1) // block - 1, 0)
        return (b, jnp.minimum(j, live), 0)

    row_spec = pl.BlockSpec((None, 1, width),
                            lambda b, j, ln: (b, 0, 0))
    tile_spec = pl.BlockSpec((None, block, width), tile_index)
    scale_spec = pl.BlockSpec((None, block, heads), tile_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, t_len // block),
        in_specs=[row_spec, tile_spec, scale_spec, tile_spec,
                  scale_spec],
        out_specs=row_spec,
        scratch_shapes=decode_scratch_shapes(heads, depth),
    )
    out = pl.pallas_call(
        functools.partial(_dense_decode_kernel_int8, block=block,
                          heads=heads, depth=depth,
                          scale=1.0 / (depth ** 0.5)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, 1, width), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q.reshape(batch, 1, width),
      cache_k.reshape(batch, t_len, width), k_scales,
      cache_v.reshape(batch, t_len, width), v_scales)
    return out.reshape(batch, 1, heads, depth)


def dense_decode_attention_xla(q, cache_k, cache_v, k_scales,
                               v_scales, lengths):
    """The reference formulation: dequantize the gathered cache with
    an elementwise multiply and rely on XLA fusing it into the dots —
    the fallback path and the numerics oracle for the kernel. Same
    math as the einsum path in models/transformer._decode_attend."""
    batch, seq, heads, depth = q.shape
    assert seq == 1
    k_all = cache_k.astype(jnp.float32) * k_scales[..., None]
    v_all = cache_v.astype(jnp.float32) * v_scales[..., None]
    k_all = k_all.astype(q.dtype)
    v_all = v_all.astype(q.dtype)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_all,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(depth))
    key_pos = jax.lax.broadcasted_iota(
        jnp.int32, (cache_k.shape[1], 1), 0)[:, 0]
    mask = key_pos[None, :] < lengths[:, None]
    scores = jnp.where(mask[:, None, None, :], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), v_all,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def resolve_dense_decode_impl(impl: Optional[str] = None) -> str:
    """'kernel' | 'xla' | None (auto: the kernel on a TPU backend,
    the XLA formulation elsewhere)."""
    return resolve_kernel_or_xla(impl, "dense decode attention")


def dense_decode_attention(q, cache_k, cache_v, k_scales, v_scales,
                           lengths, impl: Optional[str] = None,
                           interpret: bool = False):
    """Dispatch: the in-kernel int8 dequant path or the XLA
    dequant+einsum reference (see resolve_dense_decode_impl)."""
    impl = resolve_dense_decode_impl(impl)
    if impl == "kernel":
        return dense_decode_attention_kernel(
            q, cache_k, cache_v, k_scales, v_scales, lengths,
            interpret=interpret)
    return dense_decode_attention_xla(
        q, cache_k, cache_v, k_scales, v_scales, lengths)

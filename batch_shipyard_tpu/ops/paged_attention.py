"""Pallas paged-attention decode kernel (vLLM-style block tables).

The XLA formulation of paged decode attention
(models/transformer.py:_decode_attend_paged) gathers every slot's
pages into a dense [B, max_blocks*page, H, D] view before the score
matmul — it reads the full logical table width from HBM every step,
even for slots holding ten tokens. Decode attention is HBM-bandwidth
bound, so that gather IS the step time.

This kernel reads only real pages: the block table rides Pallas scalar
prefetch (pltpu.PrefetchScalarGridSpec), the k/v page BlockSpec index
maps translate grid step j into the slot's j-th physical page id, and
Mosaic DMAs exactly that page into VMEM. Pages past a slot's live
length are skipped (the index map clamps to the slot's last live page
so the prefetched DMA never fetches garbage, and @pl.when skips the
compute). Online softmax accumulates across the (sequential) page grid
dimension in VMEM scratch — the flash-attention recurrence over the
page list.

One program handles ALL heads of one page: the pool is blocked as
[P, page, H*D], so the block's last two dims are (page, H*D) — lane
dense, and legal under Mosaic's (8, 128) block rule for bf16 and int8
alike (a per-head block would squeeze the second-minor H dimension,
which Mosaic refuses). That is also how the serving cache STORES the
pool (models/transformer.py): the reshape from [P, page, H, D] is free
only on paper — the TPU tiles the two shapes differently, so a pool
kept [P, page, H, D] was relaid out whole (a read and a write of
every page) on its way into every call. Per-head scores come from
ONE matmul against a
block-diagonal query (row h holds q_h in columns h*D..(h+1)*D, zeros
elsewhere): K[page, H*D] x q_bd[H, H*D]^T -> [page, H]. That puts
positions on sublanes and heads on lanes, which is exactly the layout
of the int8 pool's [page, H] scale tile, so dequantization is an
elementwise multiply on the scores (and on the probabilities for V)
instead of on the page.

Reference analog: none — the reference (Azure batch-shipyard) has no
serving runtime; this is net-new TPU compute-path work alongside
ops/attention.py. The block-table design follows the public
vLLM/PagedAttention scheme (PAPERS.md).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _head_block_mask(heads: int, depth: int):
    """[H, H*D] True on head h's own D columns — the block diagonal."""
    row = jax.lax.broadcasted_iota(jnp.int32, (heads, heads * depth), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (heads, heads * depth), 1)
    return (col >= row * depth) & (col < (row + 1) * depth)


def _heads_to_sublanes(row, heads: int):
    """[1, H] (heads on lanes) -> [H, 1] (heads on sublanes) with
    iota/select/lane-reduce only: Mosaic has no cheap general
    lane->sublane relayout, and this one is a single (H, H) tile."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (heads, heads), 0) ==
           jax.lax.broadcasted_iota(jnp.int32, (heads, heads), 1))
    return jnp.sum(
        jnp.where(eye, jnp.broadcast_to(row, (heads, heads)), 0.0),
        axis=1, keepdims=True)


def decode_block_step(length, q_ref, k_ref, ks_ref, v_ref, vs_ref,
                      o_ref, o_acc, m_acc, l_acc, *, block: int,
                      heads: int, depth: int, scale: float):
    """One (slot, key-block) program of single-token decode attention
    over all heads — the body shared by the paged kernel here and the
    dense int8 kernel (ops/decode_attention.py); a fix to the
    mask/correction/denominator logic lands in both.

    q_ref/o_ref: [1, H*D]. k_ref/v_ref: [block, H*D] (fp or int8).
    ks_ref/vs_ref: [block, H] fp32 scales for int8 tiles, else None.
    Scratch persists across the sequential key-block grid dimension
    (program_id(1)): o_acc [H, H*D] fp32 numerator (only its block
    diagonal is meaningful), m_acc/l_acc [1, H] running max /
    denominator. ``length`` counts the slot's valid keys."""
    j = pl.program_id(1)
    num_blocks = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    @pl.when(j * block < length)
    def _accumulate():
        q = q_ref[...]
        # The select runs in fp32: a bf16 operand under an i1 mask of
        # 32-bit layout is a relayout Mosaic rejects.
        q_bd = jnp.where(
            _head_block_mask(heads, depth),
            jnp.broadcast_to(q.astype(jnp.float32),
                             (heads, heads * depth)),
            0.0).astype(q.dtype)
        scores = jax.lax.dot_general(
            k_ref[...].astype(q.dtype), q_bd,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [block, H]
        if ks_ref is not None:
            scores = scores * ks_ref[...]
        scores = scores * scale
        pos = j * block + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 0)
        scores = jnp.where(pos < length, scores, _NEG_INF)
        m_prev = m_acc[...]                              # [1, H]
        m_new = jnp.maximum(
            m_prev, jnp.max(scores, axis=0, keepdims=True))
        correction = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)                      # [block, H]
        l_acc[...] = (l_acc[...] * correction +
                      jnp.sum(p, axis=0, keepdims=True))
        m_acc[...] = m_new
        if vs_ref is not None:
            p = p * vs_ref[...]
        pv = jax.lax.dot_general(
            p.astype(q.dtype), v_ref[...].astype(q.dtype),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [H, H*D]
        o_acc[...] = (o_acc[...] *
                      _heads_to_sublanes(correction, heads) + pv)

    @pl.when(j == num_blocks - 1)
    def _emit():
        l_final = l_acc[...]
        denom = _heads_to_sublanes(
            jnp.where(l_final == 0.0, 1.0, l_final), heads)
        out = jnp.where(_head_block_mask(heads, depth),
                        o_acc[...] / denom, 0.0)
        # Each column has exactly one live row (its head's): the
        # sublane sum folds the block diagonal into [1, H*D].
        o_ref[...] = jnp.sum(out, axis=0,
                             keepdims=True).astype(o_ref.dtype)


def decode_scratch_shapes(heads: int, depth: int) -> list:
    """VMEM scratch decode_block_step expects, in argument order."""
    return [pltpu.VMEM((heads, heads * depth), jnp.float32),
            pltpu.VMEM((1, heads), jnp.float32),
            pltpu.VMEM((1, heads), jnp.float32)]


def _paged_decode_kernel(table_ref, len_ref, q_ref, k_ref, v_ref,
                         o_ref, *scratch, **static):
    decode_block_step(len_ref[pl.program_id(0)], q_ref, k_ref, None,
                      v_ref, None, o_ref, *scratch, **static)


def _paged_decode_kernel_int8(table_ref, len_ref, q_ref, k_ref,
                              ks_ref, v_ref, vs_ref, o_ref, *scratch,
                              **static):
    decode_block_step(len_ref[pl.program_id(0)], q_ref, k_ref, ks_ref,
                      v_ref, vs_ref, o_ref, *scratch, **static)


def paged_decode_attention_kernel(q, k_pages, v_pages, block_table,
                                  lengths, k_scales=None,
                                  v_scales=None):
    """Pallas path. q: [B, 1, H, D]; k_pages/v_pages:
    [P, page, H*D]; block_table: [B, max_blocks] int32; lengths: [B]
    int32 valid-key counts (INCLUDING the token written this step, so
    every attended slot has length >= 1 — a length-0 slot yields zeros
    here but softmax-of-all-masked garbage from the XLA path; the
    decode contract never attends an unwritten slot). The grid is
    (B, max_blocks) whatever the lengths: a slot computes
    ceil(length / page) of its blocks and steps over the rest. A slot
    that holds no request is still a row of the batch; the serving
    step programs park its cursor at 0 (models/inference.
    _park_idle_cursors), so it arrives here with length 1 and costs
    one block of the scratch page, not its last request's length.
    k_scales/v_scales: [P, page, H] fp32 when the pages are int8
    (applied in-kernel per tile). Returns [B, 1, H, D] in q.dtype."""
    batch, seq, heads, depth = q.shape
    assert seq == 1, "decode consumes one token per call"
    page = k_pages.shape[1]
    max_blocks = block_table.shape[1]
    width = heads * depth
    int8_pages = k_scales is not None

    def page_index(b, j, tbl, ln):
        # Clamp dead steps to the slot's LAST live page: the prefetch
        # pipeline fetches block j+1 while computing block j, and an
        # unclamped map would DMA whatever stale id sits in the dead
        # tail of the table row. Page 0 fallback covers length == 0.
        live = jnp.maximum((ln[b] + page - 1) // page - 1, 0)
        return (tbl[b, jnp.minimum(j, live)], 0, 0)

    row_spec = pl.BlockSpec((None, 1, width),
                            lambda b, j, tbl, ln: (b, 0, 0))
    page_spec = pl.BlockSpec((None, page, width), page_index)
    scale_spec = pl.BlockSpec((None, page, heads), page_index)
    in_specs = [row_spec, page_spec]
    operands = [q.reshape(batch, 1, width), k_pages]
    if int8_pages:
        in_specs.append(scale_spec)
        operands.append(k_scales)
    in_specs.append(page_spec)
    operands.append(v_pages)
    if int8_pages:
        in_specs.append(scale_spec)
        operands.append(v_scales)
    kern = (_paged_decode_kernel_int8 if int8_pages
            else _paged_decode_kernel)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, max_blocks),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=decode_scratch_shapes(heads, depth),
    )
    out = pl.pallas_call(
        functools.partial(kern, block=page, heads=heads, depth=depth,
                          scale=1.0 / (depth ** 0.5)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, 1, width), q.dtype),
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)
    return out.reshape(batch, 1, heads, depth)


def masked_attention(q, k_all, v_all, mask, dtype):
    """Softmax attention of q [B, S, H, D] over cached rows k_all /
    v_all [B, T, Hkv, D] under mask [B, 1, S, T] (True = visible):
    float32 scores and accumulation, the probabilities in ``dtype``.
    With fewer K/V heads than query heads, H // Hkv query heads read
    each K/V head and no K/V row is repeated."""
    batch, seq, heads, depth = q.shape
    kv_heads = k_all.shape[2]
    scale = jnp.sqrt(jnp.float32(depth))
    if kv_heads == heads:
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_all,
            preferred_element_type=jnp.float32)
        scores = jnp.where(mask, scores / scale, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", probs.astype(dtype), v_all,
            preferred_element_type=jnp.float32)
        return out.astype(dtype)
    grouped = q.reshape(batch, seq, kv_heads, heads // kv_heads, depth)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", grouped, k_all,
        preferred_element_type=jnp.float32)
    scores = jnp.where(mask[:, :, None], scores / scale, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", probs.astype(dtype), v_all,
        preferred_element_type=jnp.float32)
    return out.reshape(batch, seq, heads, depth).astype(dtype)


def paged_decode_attention_xla(q, k_pages, v_pages, block_table,
                               lengths, k_scales=None,
                               v_scales=None):
    """XLA gather formulation (the CPU/fallback path): materialize each
    slot's full logical [max_blocks*page, H, D] view, then one masked
    softmax. Same math as the kernel; reads the whole table width.
    With int8 pages, only the GATHERED slices dequantize — never the
    whole pool. The pool may hold FEWER K/V heads than q has query
    heads (its rows are Hkv*D wide): masked_attention groups the
    query heads over them."""
    batch, seq, heads, depth = q.shape
    assert seq == 1
    page = k_pages.shape[1]
    max_blocks = block_table.shape[1]
    kv_heads = k_pages.shape[2] // depth
    k_all = k_pages[block_table].reshape(
        batch, max_blocks * page, kv_heads, depth)
    v_all = v_pages[block_table].reshape(
        batch, max_blocks * page, kv_heads, depth)
    if k_scales is not None:
        ks = k_scales[block_table].reshape(
            batch, max_blocks * page, kv_heads)
        vs = v_scales[block_table].reshape(
            batch, max_blocks * page, kv_heads)
        k_all = (k_all.astype(jnp.float32) *
                 ks[..., None]).astype(q.dtype)
        v_all = (v_all.astype(jnp.float32) *
                 vs[..., None]).astype(q.dtype)
    key_pos = jax.lax.broadcasted_iota(
        jnp.int32, (max_blocks * page, 1), 0)[:, 0]
    mask = (key_pos[None, :] < lengths[:, None])[:, None, None, :]
    return masked_attention(q, k_all, v_all, mask, q.dtype)


def resolve_kernel_or_xla(impl: Optional[str], what: str) -> str:
    """The decode kernels' selection rule: None -> 'kernel' (Pallas)
    on a TPU backend, 'xla' elsewhere; a named impl passes through."""
    if impl is None:
        return "kernel" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("kernel", "xla"):
        raise ValueError(f"unknown {what} impl {impl!r}")
    return impl


def resolve_paged_impl(impl: Optional[str] = None) -> str:
    return resolve_kernel_or_xla(impl, "paged attention")


def paged_decode_attention(q, k_pages, v_pages, block_table, lengths,
                           impl: Optional[str] = None,
                           k_scales=None, v_scales=None):
    """Dispatch: 'kernel' (Pallas) or 'xla' (resolve_paged_impl).
    k_scales/v_scales switch both paths to int8-page dequant. A pool
    of fewer K/V heads than query heads takes the xla path whatever
    ``impl`` says: the kernel's block-diagonal query is one K/V head
    a query head."""
    grouped = k_pages.shape[2] != q.shape[2] * q.shape[3]
    fn = (paged_decode_attention_kernel
          if resolve_paged_impl(impl) == "kernel" and not grouped
          else paged_decode_attention_xla)
    return fn(q, k_pages, v_pages, block_table, lengths,
              k_scales=k_scales, v_scales=v_scales)

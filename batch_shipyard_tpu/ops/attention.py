"""Attention kernels: reference, blockwise (memory-efficient), and
Pallas flash-attention forward+backward kernels for the TPU MXU.

Layout convention throughout: q/k/v are [batch, seq, heads, head_dim]
(bfloat16 on TPU; accumulation in float32).

  - ``mha_reference``: O(T^2) materialized-scores attention, the
    correctness oracle.
  - ``blockwise_mha``: lax.scan over KV blocks with online softmax —
    O(T) memory, fully differentiable (the building block ring
    attention runs per step). This is the XLA-friendly formulation:
    static shapes, no data-dependent control flow.
  - ``flash_attention``: Pallas TPU kernels for forward AND backward.
    Forward: grid over batch*heads x q-blocks, KV streamed through
    VMEM, logsumexp rows saved. Backward: a single fused kernel (grid
    over kv-blocks, streaming Q) producing dk/dv per block while dq
    accumulates in a VMEM fp32 scratch across the sequential grid —
    P is reconstructed from the saved logsumexp exactly once per
    (q, kv) tile, which matters because the backward is exp/VPU-bound
    on v5e. ~6x faster than the autodiff-of-blockwise backward.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# Default flash kernel tiles (tuned on v5e; see bench history). The
# dispatcher guard and ring_attention's tiling check both derive from
# these — change them in one place only.
FLASH_BLOCK_Q = 512
FLASH_BLOCK_K = 1024


def flash_shapes_ok(t_q: int, t_kv: int) -> bool:
    """Can the default flash blocks tile these sequence lengths?
    Blocks clamp to the sequence, so short sequences are fine only if
    they are themselves MXU-tileable (128-aligned)."""
    def ok(t, block):
        if t < block:
            return t % 128 == 0
        return t % block == 0
    return ok(t_q, FLASH_BLOCK_Q) and ok(t_kv, FLASH_BLOCK_K)


def block_end(positions, block: int):
    """The last position of the block of ``block`` positions (a power
    of two) that each of ``positions`` lies in: what a block-causal
    mask compares a key with in place of the query's own position.
    ``block`` 0: the positions themselves (plain causal)."""
    if not block:
        return positions
    if block & (block - 1):
        raise ValueError(f"block {block} is not a power of two")
    return positions | (block - 1)


def _causal_mask(q_positions, k_positions, window: int = 0,
                 block: int = 0):
    """[Tq, Tk] True where attention is allowed: k <= q, and under a
    ``window`` also k > q - window (the query's own key included, so a
    query sees its ``window`` newest keys). ``block`` > 0 is the
    BLOCK-causal mask: key j is visible to query i iff
    j // block <= i // block (causal across blocks of ``block``
    positions, every key of the query's own block visible)."""
    mask = block_end(q_positions, block)[:, None] >= k_positions[None, :]
    if window:
        mask &= k_positions[None, :] > q_positions[:, None] - window
    return mask


def _grouped_scores(q, k):
    """q [B, Tq, H, D] against k [B, Tk, Hkv, D] -> float32
    [B, H, Tq, Tk]; H // Hkv query heads read each K/V head and no
    K/V row is repeated."""
    heads, kv_heads = q.shape[2], k.shape[2]
    if kv_heads == heads:
        return jnp.einsum("bqhd,bkhd->bhqk", q, k,
                          preferred_element_type=jnp.float32)
    grouped = q.reshape(*q.shape[:2], kv_heads, heads // kv_heads,
                        q.shape[3])
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", grouped, k,
                        preferred_element_type=jnp.float32)
    return scores.reshape(q.shape[0], heads, q.shape[1], k.shape[1])


def _grouped_values(p, v):
    """p [B, H, Tq, Tk] against v [B, Tk, Hkv, D] -> float32
    [B, Tq, H, D], the grouping of _grouped_scores."""
    heads, kv_heads = p.shape[1], v.shape[2]
    if kv_heads == heads:
        return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                          preferred_element_type=jnp.float32)
    grouped = p.reshape(p.shape[0], kv_heads, heads // kv_heads,
                        *p.shape[2:])
    out = jnp.einsum("bhgqk,bkhd->bqhgd", grouped, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(p.shape[0], p.shape[2], heads, v.shape[3])


def mha_reference(q, k, v, causal: bool = True,
                  q_offset: int = 0, kv_offset: int = 0,
                  window: int = 0, block: int = 0):
    """Plain attention; the numerics oracle for the fast paths. k/v
    may hold fewer heads than q (grouped-query); ``window`` (causal
    only) bounds each query to its newest ``window`` keys; ``block``
    (causal only) is _causal_mask's block-causal form."""
    depth = q.shape[-1]
    scores = _grouped_scores(q, k)
    scores = scores / math.sqrt(depth)
    if causal:
        q_pos = q_offset + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[1], 1), 0)[:, 0]
        k_pos = kv_offset + jax.lax.broadcasted_iota(
            jnp.int32, (k.shape[1], 1), 0)[:, 0]
        mask = _causal_mask(q_pos, k_pos, window, block)
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return _grouped_values(probs.astype(v.dtype), v).astype(q.dtype)


# ----------------------- online-softmax accumulation -------------------

def attention_block_update(q, k_blk, v_blk, o, m, l, *, causal: bool,
                           q_offset, kv_offset, scale: float,
                           window: int = 0, block: int = 0):
    """One online-softmax accumulation step against a KV block.

    q: [B, Tq, H, D]; k_blk/v_blk: [B, Tk, Hkv, D] (Hkv divides H)
    o: [B, Tq, H, D] float32 numerator
    m: [B, H, Tq] running max; l: [B, H, Tq] running denominator.
    q_offset/kv_offset: global positions (ints or traced scalars).
    ``window`` (causal only): a query sees its newest ``window`` keys;
    ``block`` (causal only): _causal_mask's block-causal form.
    """
    scores = _grouped_scores(q, k_blk) * scale
    if causal:
        q_pos = q_offset + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[1], 1), 0)[:, 0]
        k_pos = kv_offset + jax.lax.broadcasted_iota(
            jnp.int32, (k_blk.shape[1], 1), 0)[:, 0]
        mask = _causal_mask(q_pos, k_pos, window, block)
        scores = jnp.where(mask[None, None], scores, _NEG_INF)
    m_blk = jnp.max(scores, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # exp with stable max; rows with no valid keys stay at -inf max and
    # contribute nothing (exp(-inf - -inf) handled via where).
    correction = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    if window:
        # a block wholly behind the window leaves m at -inf: its
        # exp(0) = 1 must not count
        p = jnp.where(scores > _NEG_INF / 2, p, 0.0)
    l_new = l * correction + jnp.sum(p, axis=-1)
    pv = _grouped_values(p.astype(v_blk.dtype), v_blk)
    o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
    return o_new, m_new, l_new


def attention_init(q):
    batch, t_q, heads, depth = q.shape
    o = jnp.zeros((batch, t_q, heads, depth), dtype=jnp.float32)
    m = jnp.full((batch, heads, t_q), _NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((batch, heads, t_q), dtype=jnp.float32)
    return o, m, l


def attention_finalize(q, o, m, l):
    denom = jnp.where(l == 0.0, 1.0, l).transpose(0, 2, 1)[..., None]
    return (o / denom).astype(q.dtype)


def blockwise_mha(q, k, v, causal: bool = True, block_size: int = 512,
                  q_offset: int = 0, kv_offset: int = 0,
                  window: int = 0, block: int = 0):
    """Memory-efficient attention: scan KV blocks with online softmax.
    k/v may hold fewer heads than q (grouped-query: no row repeated);
    ``window`` (causal only) is the band: a query sees its newest
    ``window`` keys; ``block`` (causal only) the block-causal mask of
    _causal_mask."""
    batch, t_kv = k.shape[0], k.shape[1]
    block_size = min(block_size, t_kv)
    if t_kv % block_size:
        raise ValueError(
            f"kv length {t_kv} not divisible by block {block_size}")
    num_blocks = t_kv // block_size
    scale = 1.0 / math.sqrt(q.shape[-1])
    k_blocks = k.reshape(batch, num_blocks, block_size, *k.shape[2:])
    v_blocks = v.reshape(batch, num_blocks, block_size, *v.shape[2:])

    # Rematerialize each block update: without this, the scan's
    # backward saves every block's score/probability matrices
    # ([B,H,Tq,block] fp32 per step — gigabytes per layer), defeating
    # the whole point of blockwise attention. With it, the backward
    # recomputes scores per block (the flash-attention property).
    @jax.checkpoint
    def step(carry, blk):
        o, m, l = carry
        k_blk, v_blk, blk_idx = blk
        o, m, l = attention_block_update(
            q, k_blk, v_blk, o, m, l, causal=causal,
            q_offset=q_offset,
            kv_offset=kv_offset + blk_idx * block_size, scale=scale,
            window=window, block=block)
        return (o, m, l), None

    carry = attention_init(q)
    (o, m, l), _ = jax.lax.scan(
        step, carry,
        (k_blocks.transpose(1, 0, 2, 3, 4),
         v_blocks.transpose(1, 0, 2, 3, 4),
         jnp.arange(num_blocks)))
    return attention_finalize(q, o, m, l)


# --------------------------- pallas forward ----------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      block_k: int, causal: bool, scale: float,
                      q_block: int):
    """One (batch*head, q-block) program: stream KV blocks via the
    grid-blocked refs and accumulate with online softmax in VMEM.
    Also emits the logsumexp rows consumed by the backward kernels."""
    qi = pl.program_id(1)
    # Operands stay in their input dtype (bf16 in production): the MXU
    # multiplies bf16 x bf16 with exact fp32 accumulation at full rate,
    # where pre-casting to fp32 forces the ~3x-slower multi-pass mode.
    q_tile = q_ref[...]  # [q_block, D]
    t_kv = k_ref.shape[0]
    num_kb = t_kv // block_k

    def make_body(masked: bool):
        def body(kb, carry):
            o, m, l = carry
            k_blk = k_ref[pl.ds(kb * block_k, block_k), :]
            v_blk = v_ref[pl.ds(kb * block_k, block_k), :]
            scores = jax.lax.dot_general(
                q_tile, k_blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [qb, kb]
            if masked:
                q_pos = (qi * q_block + jax.lax.broadcasted_iota(
                    jnp.int32, (q_block, block_k), 0))
                k_pos = (kb * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (q_block, block_k), 1))
                scores = jnp.where(q_pos >= k_pos, scores, _NEG_INF)
            m_blk = jnp.max(scores, axis=-1)
            m_new = jnp.maximum(m, m_blk)
            correction = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new[:, None])
            l_new = l * correction + jnp.sum(p, axis=-1)
            pv = jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return o * correction[:, None] + pv, m_new, l_new

        return body

    o = jnp.zeros((q_block, q_ref.shape[-1]), dtype=jnp.float32)
    m = jnp.full((q_block,), _NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((q_block,), dtype=jnp.float32)
    if causal:
        # KV blocks fully below the diagonal need no mask; only blocks
        # straddling it do, and blocks past it contribute nothing
        # (exact ceil — the old floor+1 bound ran a fully-masked
        # wasted block whenever the division was exact).
        n_full = qi * q_block // block_k
        upper = jnp.minimum(
            num_kb, ((qi + 1) * q_block + block_k - 1) // block_k)
        o, m, l = jax.lax.fori_loop(0, n_full, make_body(False),
                                    (o, m, l))
        o, m, l = jax.lax.fori_loop(n_full, upper, make_body(True),
                                    (o, m, l))
    else:
        o, m, l = jax.lax.fori_loop(0, num_kb, make_body(False),
                                    (o, m, l))
    denom = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (o / denom[:, None]).astype(o_ref.dtype)
    lse_ref[...] = (m + jnp.log(denom))[:, None]


def _flash_forward(q, k, v, causal: bool, block_q: int, block_k: int,
                   with_lse: bool = False):
    batch, t_q, heads, depth = q.shape
    t_kv = k.shape[1]
    scale = 1.0 / math.sqrt(depth)
    # Collapse batch/heads into the grid's first dimension.
    q_r = q.transpose(0, 2, 1, 3).reshape(batch * heads, t_q, depth)
    k_r = k.transpose(0, 2, 1, 3).reshape(batch * heads, t_kv, depth)
    v_r = v.transpose(0, 2, 1, 3).reshape(batch * heads, t_kv, depth)
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_kv)
    if t_q % block_q or t_kv % block_k:
        raise ValueError(
            f"flash attention requires seq lengths divisible by block "
            f"sizes: t_q={t_q} block_q={block_q}, t_kv={t_kv} "
            f"block_k={block_k}")
    grid = (batch * heads, t_q // block_q)
    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, block_k=block_k,
                          causal=causal, scale=scale, q_block=block_q),
        out_shape=(
            jax.ShapeDtypeStruct((batch * heads, t_q, depth), q.dtype),
            # Trailing singleton keeps the block 2D for the TPU
            # tiling rules (lane dim == full array dim of 1).
            jax.ShapeDtypeStruct((batch * heads, t_q, 1),
                                 jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, depth),
                         lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, t_kv, depth), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((None, t_kv, depth), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((None, block_q, depth),
                         lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, block_q, 1),
                         lambda bh, qi: (bh, qi, 0)),
        ),
    )(q_r, k_r, v_r)
    out = out.reshape(batch, heads, t_q, depth).transpose(0, 2, 1, 3)
    if with_lse:
        # lse stays [B*H, T, 1] (trailing singleton for TPU tiling)
        # for the backward kernels.
        return out, lse
    return out


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, *,
                      block_q: int, causal: bool, scale: float,
                      k_block: int):
    """Fused backward for one (batch*head, kv-block): stream Q blocks.

    dV = P^T @ dO; dK = scale * dS^T @ Q — and dQ accumulates into a
    VMEM fp32 scratch across the (sequential) kv-block grid dimension,
    so P = exp(S - lse) and the score matmul are computed ONCE per
    (q, kv) tile instead of once in a dq kernel and again in a dkv
    kernel. On a v5e chip the backward is exp/VPU-bound, so the fusion
    is worth ~1.5x on the whole backward.
    """
    kb = pl.program_id(1)
    num_kb = pl.num_programs(1)
    k_tile = k_ref[...]
    v_tile = v_ref[...]
    t_q = q_ref.shape[0]
    num_qb = t_q // block_q

    @pl.when(kb == 0)
    def _zero_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def make_body(masked: bool):
        def body(qi, carry):
            dk, dv = carry
            q_blk = q_ref[pl.ds(qi * block_q, block_q), :]
            do_blk = do_ref[pl.ds(qi * block_q, block_q), :]
            lse_blk = lse_ref[pl.ds(qi * block_q, block_q), 0]
            delta_blk = delta_ref[pl.ds(qi * block_q, block_q), 0]
            scores = jax.lax.dot_general(
                q_blk, k_tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [qb, kb]
            if masked:
                q_pos = (qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, k_block), 0))
                k_pos = (kb * k_block + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, k_block), 1))
                scores = jnp.where(q_pos >= k_pos, scores, _NEG_INF)
            p = jnp.exp(scores - lse_blk[:, None])  # [qb, kb]
            dv = dv + jax.lax.dot_general(
                p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [kb, D]
            dp = jax.lax.dot_general(
                do_blk, v_tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [qb, kb]
            ds = p * (dp - delta_blk[:, None])
            dk = dk + jax.lax.dot_general(
                ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [kb, D]
            dq_blk = jax.lax.dot_general(
                ds.astype(k_tile.dtype), k_tile,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [qb, D]
            dq_acc[pl.ds(qi * block_q, block_q), :] = (
                dq_acc[pl.ds(qi * block_q, block_q), :] + dq_blk)
            return dk, dv

        return body

    if causal:
        # Q blocks strictly before the diagonal see nothing of this
        # KV block; blocks past the diagonal need no mask at all.
        lower = (kb * k_block) // block_q
        first_full = ((kb + 1) * k_block + block_q - 1) // block_q
    else:
        lower = 0
        first_full = 0
    zeros = (jnp.zeros((k_block, k_ref.shape[-1]), dtype=jnp.float32),
             jnp.zeros((k_block, v_ref.shape[-1]), dtype=jnp.float32))
    dk, dv = jax.lax.fori_loop(
        lower, jnp.minimum(first_full, num_qb),
        make_body(masked=causal), zeros)
    dk, dv = jax.lax.fori_loop(
        jnp.maximum(lower, jnp.minimum(first_full, num_qb)), num_qb,
        make_body(masked=False), (dk, dv))
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(kb == num_kb - 1)
    def _emit_dq():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal: bool, block_q: int,
                    block_k: int, g_lse=None):
    batch, t_q, heads, depth = q.shape
    t_kv = k.shape[1]
    scale = 1.0 / math.sqrt(depth)
    # The fused kernel keeps more live [block_q, block_k] fp32
    # temporaries than the forward (p, dp, ds + casts), so its q-block
    # is halved — and the k-block too for fp32 inputs, whose resident
    # Q/dO/KV buffers are twice the size — to stay inside the ~16MB
    # VMEM scoped-stack budget.
    block_q = min(block_q, t_q, 256)
    block_k = min(block_k, t_kv)
    if jnp.dtype(q.dtype).itemsize >= 4:
        block_k = min(block_k, 512)
    bh = batch * heads
    q_r = q.transpose(0, 2, 1, 3).reshape(bh, t_q, depth)
    k_r = k.transpose(0, 2, 1, 3).reshape(bh, t_kv, depth)
    v_r = v.transpose(0, 2, 1, 3).reshape(bh, t_kv, depth)
    do_r = g.transpose(0, 2, 1, 3).reshape(bh, t_q, depth)
    o_r = out.transpose(0, 2, 1, 3).reshape(bh, t_q, depth)
    # delta = rowsum(dO * O), the softmax-normalizer correction term.
    delta = jnp.sum(do_r.astype(jnp.float32) * o_r.astype(jnp.float32),
                    axis=-1, keepdims=True)
    if g_lse is not None:
        # Cotangent on the lse output enters the score gradient as
        # ds_j = p_j * (dP_j - delta + g_lse)  — because d lse/d s_j
        # = p_j — i.e. exactly a correction to delta. This is what
        # makes the ring merge (whose weights depend on each block's
        # lse) differentiate correctly through the per-block kernels.
        delta = delta - g_lse.astype(jnp.float32)
    q_full = pl.BlockSpec((None, t_q, depth), lambda b, i: (b, 0, 0))
    row_full = pl.BlockSpec((None, t_q, 1), lambda b, i: (b, 0, 0))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, block_q=block_q,
                          causal=causal, scale=scale, k_block=block_k),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t_q, depth), q.dtype),
            jax.ShapeDtypeStruct((bh, t_kv, depth), k.dtype),
            jax.ShapeDtypeStruct((bh, t_kv, depth), v.dtype),
        ),
        grid=(bh, t_kv // block_k),
        in_specs=[
            q_full,
            pl.BlockSpec((None, block_k, depth),
                         lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, depth),
                         lambda b, i: (b, i, 0)),
            q_full,
            row_full, row_full,
        ],
        out_specs=(
            q_full,
            pl.BlockSpec((None, block_k, depth),
                         lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, depth),
                         lambda b, i: (b, i, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((t_q, depth), jnp.float32)],
    )(q_r, k_r, v_r, do_r, lse, delta)

    def unflatten(x, t_len):
        return x.reshape(batch, heads, t_len, depth).transpose(
            0, 2, 1, 3)

    return (unflatten(dq, t_q), unflatten(dk, t_kv),
            unflatten(dv, t_kv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = FLASH_BLOCK_Q,
                    block_k: int = FLASH_BLOCK_K):
    """Pallas flash attention: hand kernels for forward AND backward
    (dq + dkv kernels over saved logsumexp rows)."""
    return _flash_forward(q, k, v, causal, block_q, block_k)


def _flash_fwd_rule(q, k, v, causal, block_q, block_k):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k,
                              with_lse=True)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, block_q, block_k, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_backward(q, k, v, out, lse, g, causal, block_q,
                           block_k)


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention_with_lse(q, k, v, causal: bool = True,
                             block_q: int = FLASH_BLOCK_Q,
                             block_k: int = FLASH_BLOCK_K):
    """flash_attention variant that also returns the logsumexp rows
    ([B*H, T, 1] fp32) — the ring-attention building block (block
    results are merged across rotations in logsumexp space)."""
    return _flash_forward(q, k, v, causal, block_q, block_k,
                          with_lse=True)


def _flash_lse_fwd_rule(q, k, v, causal, block_q, block_k):
    out, lse = _flash_forward(q, k, v, causal, block_q, block_k,
                              with_lse=True)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd_rule(causal, block_q, block_k, residuals, grads):
    q, k, v, out, lse = residuals
    g, g_lse = grads
    return _flash_backward(q, k, v, out, lse, g, causal, block_q,
                           block_k, g_lse=g_lse)


flash_attention_with_lse.defvjp(_flash_lse_fwd_rule,
                                _flash_lse_bwd_rule)


def merge_attention_blocks(o1, lse1, o2, lse2):
    """Merge two normalized attention partials in logsumexp space.

    o_i: [B, T, H, D] (any float dtype); lse_i: [B*H, T, 1] fp32 with
    -inf marking fully-masked rows. Returns (o, lse) of the combined
    attention over the union of the two key sets.
    """
    batch, t_len, heads, depth = o1.shape
    l1 = lse1.reshape(batch, heads, t_len).transpose(0, 2, 1)
    l2 = lse2.reshape(batch, heads, t_len).transpose(0, 2, 1)
    m = jnp.maximum(l1, l2)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    w1 = jnp.where(l1 > _NEG_INF / 2, jnp.exp(l1 - m_safe), 0.0)
    w2 = jnp.where(l2 > _NEG_INF / 2, jnp.exp(l2 - m_safe), 0.0)
    denom = w1 + w2
    denom_safe = jnp.where(denom == 0.0, 1.0, denom)
    o = (o1.astype(jnp.float32) * (w1 / denom_safe)[..., None] +
         o2.astype(jnp.float32) * (w2 / denom_safe)[..., None])
    lse = jnp.where(denom > 0.0, m_safe + jnp.log(denom_safe),
                    _NEG_INF)
    lse = lse.transpose(0, 2, 1).reshape(batch * heads, t_len, 1)
    return o.astype(o1.dtype), lse


def masked_attention_block(q):
    """The identity element for merge_attention_blocks: zero output,
    -inf logsumexp (no keys visible)."""
    batch, t_len, heads, _depth = q.shape
    return (jnp.zeros_like(q),
            jnp.full((batch * heads, t_len, 1), _NEG_INF, jnp.float32))


def resolve_attention_impl(impl: Optional[str], t_q: int,
                           t_kv: int) -> str:
    """None -> 'flash' on a TPU backend when the default flash blocks
    tile the sequence lengths, else 'blockwise' (untileable lengths
    on TPU, and every other backend)."""
    if impl is None:
        if jax.default_backend() == "tpu" and flash_shapes_ok(t_q,
                                                              t_kv):
            return "flash"
        return "blockwise"
    if impl not in ("flash", "blockwise", "reference"):
        raise ValueError(f"unknown attention impl {impl!r}")
    return impl


def attention(q, k, v, causal: bool = True,
              impl: Optional[str] = None, block_size: int = 512):
    """Dispatch: 'flash' (pallas fwd+bwd), 'blockwise', or
    'reference'; None resolves via resolve_attention_impl."""
    if impl is None:
        impl = resolve_attention_impl(None, q.shape[1], k.shape[1])
        if impl == "blockwise" and k.shape[1] % min(block_size,
                                                    k.shape[1]):
            block_size = math.gcd(k.shape[1], block_size)
    if impl == "flash":
        return flash_attention(q, k, v, causal)
    if impl == "blockwise":
        return blockwise_mha(q, k, v, causal, block_size=block_size)
    if impl == "reference":
        return mha_reference(q, k, v, causal)
    raise ValueError(f"unknown attention impl {impl!r}")


# ------------------- prefill over a cache, in blocks -------------------
# A serving prefill (models/transformer.py, the multi-token insert of
# a decode-mode model) has written a segment's K/V rows into a cache
# [B, T, Hkv*D] and asks for the segment's queries against the rows
# its mask admits: key j is visible to the query at position i iff
# j <= i and (no window or j > i - window). Masked scores over the
# whole cache width ([S, T] a head) are what that path materialised;
# here the work is bounded by what is visible: the key blocks from the
# first the window still touches to the last the segment reaches.

PREFILL_BLOCK_Q = 256
PREFILL_BLOCK_K = 512
PREFILL_KERNEL_NAME = "flash_prefill_cached"


def kept_in(x, dtype):
    """A float32 term of a softmax (a score, the running maximum,
    denominator or weighted sum) as it reads once KEPT in ``dtype``:
    itself in float32; rounded to it and back in bfloat16, the
    nearest precision below, which a check's control switches on."""
    return x if jnp.dtype(dtype) == jnp.float32 else x.astype(
        dtype).astype(jnp.float32)


def _band_blocks(start, first_q: int, last_q: int, window: int,
                 block_k: int, num_kb):
    """(first, last) key block a query block at positions start +
    first_q .. start + last_q reads (inclusive)."""
    low = jnp.maximum(start + first_q - window + 1, 0) if window else 0
    first = low // block_k
    last = jnp.minimum((start + last_q) // block_k, num_kb - 1)
    return first, last


def cached_prefill_attention_xla(q, k_cache, v_cache, start,
                                 window: int = 0,
                                 block_k: int = PREFILL_BLOCK_K,
                                 softmax_dtype=jnp.float32,
                                 block: int = 0, v_depth: int = 0,
                                 scale: Optional[float] = None):
    """q [B, S, H, D] at positions start[b] .. start[b] + S - 1
    against cache rows k_cache / v_cache [B, T, Hkv*D] -> [B, S, H, D].
    ``v_depth`` > 0: values of another depth than q and k (v_cache
    [B, T, Hkv*v_depth] -> [B, S, H, v_depth]); ``scale``: the scores'
    factor where it is not 1 / sqrt(D) (keys that carry a lane tile's
    fill: prefill_key_depth).
    A loop (dynamic trip count) over the key blocks between the first
    the window still touches for the segment's first query and the
    last its last query reaches, online softmax across them (its
    scores and running terms kept in ``softmax_dtype``:
    kept_in): the XLA formulation, and the oracle
    of the kernel below. ``block`` > 0: the block-causal mask
    (_causal_mask's: a query sees every key up to the end of its own
    block of ``block`` positions; ``start`` is then a whole number of
    blocks)."""
    batch, seq, heads, depth = q.shape
    rows = k_cache.shape[1]
    kv_heads = k_cache.shape[2] // depth
    block_k = math.gcd(rows, block_k)
    scale = 1.0 / math.sqrt(depth) if scale is None else scale
    start = jnp.asarray(start, jnp.int32).reshape(-1)
    # from the first block the earliest query's band touches to the
    # last the latest query reaches
    first, _ = _band_blocks(jnp.min(start), 0, 0, window, block_k,
                            rows // block_k)
    _, last = _band_blocks(jnp.max(start), 0,
                           block_end(seq - 1, block), window, block_k,
                           rows // block_k)
    q_pos = start[:, None] + jnp.arange(seq, dtype=jnp.int32)[None]

    def body(kb, carry):
        k_blk, v_blk = (jax.lax.dynamic_slice_in_dim(
            cache, kb * block_k, block_k, axis=1).reshape(
                batch, block_k, kv_heads, -1)
            for cache in (k_cache, v_cache))
        k_pos = kb * block_k + jnp.arange(block_k, dtype=jnp.int32)
        mask = k_pos[None, None, :] <= block_end(q_pos, block)[:, :, None]
        if window:
            mask &= k_pos[None, None, :] > q_pos[:, :, None] - window
        o, m, l = carry
        scores = jnp.where(mask[:, None], kept_in(
            _grouped_scores(q, k_blk) * scale, softmax_dtype), _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        correction = jnp.exp(m - m_new)
        p = jnp.where(mask[:, None],
                      jnp.exp(scores - m_new[..., None]), 0.0)
        l = l * correction + jnp.sum(p, axis=-1)
        o = o * correction.transpose(0, 2, 1)[..., None] + \
            _grouped_values(p.astype(v_blk.dtype), v_blk)
        return (kept_in(o, softmax_dtype), m_new,
                kept_in(l, softmax_dtype))

    o, m, l = jax.lax.fori_loop(
        first, last + 1, body,
        attention_init(q[..., :v_depth] if v_depth else q))
    return attention_finalize(q, o, m, l)


def _flash_prefill_kernel(start_ref, q_ref, k_ref, v_ref, o_ref,
                          o_acc, m_acc, l_acc, *, block_q: int,
                          block_k: int, group: int, depth: int,
                          window: int, num_kb: int, scale: float,
                          softmax_dtype, block: int = 0):
    """One (batch, K/V head, query block, key step) program: the
    query block's ``group`` heads, stacked along the rows, against one
    key block; online softmax across the key steps in VMEM scratch.
    Key step j reads block first + j; steps past the last block the
    query block reaches compute nothing (their index map clamps, so
    nothing is fetched for them either)."""
    b, qi, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    start = start_ref[b]
    first, last = _band_blocks(start, qi * block_q,
                               (qi + 1) * block_q - 1, window, block_k,
                               num_kb)

    @pl.when(j == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    @pl.when(first + j <= last)
    def _accumulate():
        q = q_ref[...]                               # [bq, G*D]
        stacked = jnp.concatenate(
            [q[:, g * depth:(g + 1) * depth] for g in range(group)],
            axis=0)                                  # [G*bq, D]
        scores = jax.lax.dot_general(
            stacked, k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        scores = kept_in(scores, softmax_dtype)
        q_pos = start + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = (first + j) * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos <= block_end(q_pos, block)
        if window:
            mask &= k_pos > q_pos - window
        mask = jnp.concatenate([mask] * group, axis=0)
        scores = jnp.where(mask, scores, _NEG_INF)
        m_prev = m_acc[...]
        m_new = jnp.maximum(m_prev,
                            jnp.max(scores, axis=1, keepdims=True))
        correction = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
        l_acc[...] = kept_in(l_acc[...] * correction + jnp.sum(
            p, axis=1, keepdims=True), softmax_dtype)
        m_acc[...] = m_new
        o_acc[...] = kept_in(
            o_acc[...] * correction + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[...],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32), softmax_dtype)

    @pl.when(j == pl.num_programs(3) - 1)
    def _emit():
        l_final = l_acc[...]
        out = o_acc[...] / jnp.where(l_final == 0.0, 1.0, l_final)
        o_ref[...] = jnp.concatenate(
            [out[g * block_q:(g + 1) * block_q] for g in range(group)],
            axis=1).astype(o_ref.dtype)


def prefill_key_depth(depth: int, seq: int, rows: int) -> int:
    """The depth a caller whose q and k are ``depth`` deep (not whole
    lane tiles: 192) lays them out at for cached_prefill_attention:
    filled with zeros to whole tiles of 128 where the kernel will take
    the call (a zero lane adds nothing to a score, and the MXU pads a
    tile's rest itself), as they are elsewhere."""
    padded = -(-depth // 128) * 128
    return padded if (jax.default_backend() == "tpu"
                      and prefill_kernel_shapes_ok(seq, rows, padded)) \
        else depth


def prefill_kernel_shapes_ok(seq: int, rows: int, depth: int) -> bool:
    """Whether the kernel's blocks tile these shapes: lane-wide heads,
    whole query and key blocks."""
    return (depth % 128 == 0 and seq % min(PREFILL_BLOCK_Q, seq) == 0
            and seq % 8 == 0
            and rows % min(PREFILL_BLOCK_K, rows) == 0
            and min(PREFILL_BLOCK_K, rows) % 128 == 0)


def cached_prefill_attention_kernel(q, k_cache, v_cache, start,
                                    window: int = 0,
                                    interpret: bool = False,
                                    softmax_dtype=jnp.float32,
                                    block: int = 0, v_depth: int = 0,
                                    scale: Optional[float] = None):
    """cached_prefill_attention_xla as a Pallas kernel: scores stay in
    VMEM, a K/V head's ``group`` query heads share each key block's
    one read, and a query block visits only the key blocks its band
    touches (``window`` > 0: ceil((window + block_q) / block_k) + 1 of
    them whatever the cache's length). ``block``: the XLA form's; a
    query block is whole blocks of it, so the key blocks it visits
    are the causal mask's."""
    batch, seq, heads, depth = q.shape
    if block and min(PREFILL_BLOCK_Q, seq) % block:
        raise ValueError(f"a query block of {min(PREFILL_BLOCK_Q, seq)} "
                         f"rows is not whole blocks of {block}")
    rows = k_cache.shape[1]
    kv_heads = k_cache.shape[2] // depth
    group = heads // kv_heads
    block_q = min(PREFILL_BLOCK_Q, seq)
    block_k = min(PREFILL_BLOCK_K, rows)
    num_kb = rows // block_k
    out_depth = v_depth or depth
    steps = num_kb if not window else min(
        num_kb, -(-(window + block_q - 1) // block_k) + 1)

    def q_map(b, h, qi, j, start_ref):
        return (b, qi, h)

    def kv_map(b, h, qi, j, start_ref):
        first, last = _band_blocks(
            start_ref[b], qi * block_q, (qi + 1) * block_q - 1, window,
            block_k, num_kb)
        return (b, jnp.minimum(first + j, last), h)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, kv_heads, seq // block_q, steps),
        in_specs=[pl.BlockSpec((None, block_q, group * depth), q_map),
                  pl.BlockSpec((None, block_k, depth), kv_map),
                  pl.BlockSpec((None, block_k, out_depth), kv_map)],
        out_specs=pl.BlockSpec((None, block_q, group * out_depth),
                               q_map),
        scratch_shapes=[
            pltpu.VMEM((group * block_q, out_depth), jnp.float32),
            pltpu.VMEM((group * block_q, 1), jnp.float32),
            pltpu.VMEM((group * block_q, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(
            _flash_prefill_kernel, block_q=block_q, block_k=block_k,
            group=group, depth=depth, window=int(window),
            num_kb=num_kb,
            scale=1.0 / math.sqrt(depth) if scale is None else scale,
            softmax_dtype=softmax_dtype,
            **({"block": int(block)} if block else {})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, seq, heads * out_depth),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name=PREFILL_KERNEL_NAME, interpret=interpret,
    )(jnp.asarray(start, jnp.int32).reshape(-1),
      q.reshape(batch, seq, heads * depth), k_cache, v_cache)
    return out.reshape(batch, seq, heads, out_depth)


def cached_prefill_attention(q, k_cache, v_cache, start,
                             window: int = 0,
                             impl: Optional[str] = None,
                             softmax_dtype=jnp.float32,
                             block: int = 0, v_depth: int = 0,
                             scale: Optional[float] = None):
    """Dispatch: 'kernel' (Pallas) on a TPU backend where its blocks
    tile the shapes, else 'xla'; a named impl passes through.
    ``block`` > 0: block-causal; ``v_depth`` / ``scale``: values of a
    depth of their own, the scores' factor (both
    cached_prefill_attention_xla's)."""
    if impl is None:
        impl = "kernel" if (
            jax.default_backend() == "tpu" and prefill_kernel_shapes_ok(
                q.shape[1], k_cache.shape[1], q.shape[3])) else "xla"
    if impl == "kernel":
        return cached_prefill_attention_kernel(
            q, k_cache, v_cache, start, window,
            softmax_dtype=softmax_dtype, block=block,
            v_depth=v_depth, scale=scale)
    if impl != "xla":
        raise ValueError(f"unknown prefill attention impl {impl!r}")
    return cached_prefill_attention_xla(q, k_cache, v_cache, start,
                                        window,
                                        softmax_dtype=softmax_dtype,
                                        block=block, v_depth=v_depth,
                                        scale=scale)

"""Pallas paged-attention decode kernels (vLLM-style block tables).

The XLA formulation of paged decode attention
(paged_decode_attention_xla) gathers every slot's pages into a dense
[B, max_blocks*page, H, D] view before the score matmul — it reads the
full logical table width from HBM every step, even for slots holding
ten tokens. Decode attention is HBM-bandwidth bound, so that gather IS
the step time. The kernels read only real pages, and which one a call
runs is paged_decode_road's one table:

  * bf16/f32 pages of ANY pool (an MHA pool, fewer K/V heads than
    query heads, a window layer's ring): ONE program a slot that walks
    the slot's live pages a chunk at a time behind a double buffer
    (gqa_paged_decode_attention_kernel, further down).
  * int8 pages of an MHA pool: a program a (slot, table entry), the
    only kernel that reads the pages' scales
    (paged_decode_attention_kernel, next). The block table rides
    Pallas scalar prefetch (pltpu.PrefetchScalarGridSpec), the k/v
    page BlockSpec index maps translate grid step j into the slot's
    j-th physical page id, and Mosaic DMAs exactly that page into
    VMEM. Pages past a slot's live length are skipped (the index map
    clamps to the slot's last live page so the prefetched DMA never
    fetches garbage, and @pl.when skips the compute), but every
    (slot, entry) is still a grid step: bf16 pages left this kernel
    for that reason (PERF.md, PR 43). Online softmax accumulates
    across the (sequential) page grid dimension in VMEM scratch — the
    flash-attention recurrence over the page list.

Both handle ALL heads of a page at once: the pool is blocked as
[P, page, H*D], so a tile's last two dims are (page, H*D) — lane
dense, and legal under Mosaic's (8, 128) block rule for bf16 and int8
alike (a per-head block would squeeze the second-minor H dimension,
which Mosaic refuses). That is also how the serving cache STORES the
pool (models/transformer.py): the reshape from [P, page, H, D] is free
only on paper — the TPU tiles the two shapes differently, so a pool
kept [P, page, H, D] was relaid out whole (a read and a write of
every page) on its way into every call. Per-head scores come from
ONE matmul against a
block-diagonal query (row h holds q_h in columns h*D..(h+1)*D, zeros
elsewhere): in the int8 kernel K[page, H*D] x q_bd[H, H*D]^T ->
[page, H]. That puts
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

from batch_shipyard_tpu.ops.attention import kept_in

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
    over all heads of int8 keys and values — the body shared by the
    paged int8 kernel here and the dense int8 kernel
    (ops/decode_attention.py); a fix to the mask/correction/denominator
    logic lands in both.

    q_ref/o_ref: [1, H*D]. k_ref/v_ref: [block, H*D] int8.
    ks_ref/vs_ref: [block, H] fp32 scales of the tiles.
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
        scores = scores * ks_ref[...] * scale
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


def _paged_decode_kernel_int8(table_ref, len_ref, q_ref, k_ref,
                              ks_ref, v_ref, vs_ref, o_ref, *scratch,
                              **static):
    decode_block_step(len_ref[pl.program_id(0)], q_ref, k_ref, ks_ref,
                      v_ref, vs_ref, o_ref, *scratch, **static)


def paged_decode_attention_kernel(q, k_pages, v_pages, block_table,
                                  lengths, k_scales, v_scales):
    """Pallas path of an int8 MHA pool (bf16/f32 pages of any pool go
    through gqa_paged_decode_attention_kernel below, which reads no
    scales). q: [B, 1, H, D]; k_pages/v_pages: [P, page, H*D] int8;
    k_scales/v_scales: [P, page, H] fp32 (applied in-kernel per
    tile); block_table: [B, max_blocks] int32; lengths: [B]
    int32 valid-key counts (INCLUDING the token written this step). A
    slot of length 0 yields zeros, as on every road of
    paged_decode_road's table. The grid is (B, max_blocks) whatever
    the lengths: a slot computes ceil(length / page) of its blocks
    and steps over the rest. A slot that holds no request is still a
    row of the batch; the serving step hands it here with length 0
    (its ``live`` mask, Attention._decode_attend_paged), so it
    computes no block: its grid steps fetch its table's first entry
    (the scratch page) and skip.
    Returns [B, 1, H, D] in q.dtype."""
    batch, seq, heads, depth = q.shape
    assert seq == 1, "decode consumes one token per call"
    page = k_pages.shape[1]
    max_blocks = block_table.shape[1]
    width = heads * depth

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
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(batch, max_blocks),
        in_specs=[row_spec, page_spec, scale_spec, page_spec,
                  scale_spec],
        out_specs=row_spec,
        scratch_shapes=decode_scratch_shapes(heads, depth),
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel_int8, block=page,
                          heads=heads, depth=depth,
                          scale=1.0 / (depth ** 0.5)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, 1, width), q.dtype),
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(batch, 1, width), k_pages, k_scales, v_pages, v_scales)
    return out.reshape(batch, 1, heads, depth)


def masked_attention(q, k_all, v_all, mask, dtype,
                     softmax_dtype=jnp.float32):
    """Softmax attention of q [B, S, H, D] over cached rows k_all /
    v_all [B, T, Hkv, D] under mask [B, 1, S, T] (True = visible):
    float32 scores and accumulation (kept in ``softmax_dtype``:
    kept_in), the probabilities in ``dtype``.
    With fewer K/V heads than query heads, H // Hkv query heads read
    each K/V head and no K/V row is repeated."""
    batch, seq, heads, depth = q.shape
    kv_heads = k_all.shape[2]
    scale = jnp.sqrt(jnp.float32(depth))
    if kv_heads == heads:
        scores = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_all,
            preferred_element_type=jnp.float32)
        scores = jnp.where(mask, kept_in(scores / scale, softmax_dtype),
                           _NEG_INF)
        probs = kept_in(jax.nn.softmax(scores, axis=-1), softmax_dtype)
        out = jnp.einsum(
            "bhqk,bkhd->bqhd", probs.astype(dtype), v_all,
            preferred_element_type=jnp.float32)
        return out.astype(dtype)
    grouped = q.reshape(batch, seq, kv_heads, heads // kv_heads, depth)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", grouped, k_all,
        preferred_element_type=jnp.float32)
    scores = jnp.where(mask[:, :, None],
                       kept_in(scores / scale, softmax_dtype), _NEG_INF)
    probs = kept_in(jax.nn.softmax(scores, axis=-1), softmax_dtype)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", probs.astype(dtype), v_all,
        preferred_element_type=jnp.float32)
    return out.reshape(batch, seq, heads, depth).astype(dtype)


def _zero_where_empty(out, lengths):
    """The gathers' half of "a slot of length 0 yields zeros": a
    softmax over no visible key is a mean of whatever the table
    points at, where the kernels write zeros. out: [B, S, H, D]."""
    return jnp.where((lengths > 0)[:, None, None, None], out, 0)


def paged_decode_attention_xla(q, k_pages, v_pages, block_table,
                               lengths, k_scales=None,
                               v_scales=None):
    """XLA gather formulation (the CPU/fallback path): materialize each
    slot's full logical [max_blocks*page, H, D] view, then one masked
    softmax. Same math as the kernel; reads the whole table width.
    With int8 pages, only the GATHERED slices dequantize — never the
    whole pool. The pool may hold FEWER K/V heads than q has query
    heads (its rows are Hkv*D wide): masked_attention groups the
    query heads over them. A slot of length 0 yields zeros."""
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
    return _zero_where_empty(
        masked_attention(q, k_all, v_all, mask, q.dtype), lengths)


# ---------------------------------------------------------------------
# bf16/f32 pages of any pool: as many K/V heads as query heads (MHA) or
# FEWER (grouped-query attention), a layer that sees only its newest
# ``window`` keys, a slot-owned RING of pages: one kernel, named apart
# from the one above in a device trace (gqa_paged_decode) but for an
# MHA pool's one-token call, which keeps its caller's name there
# (paged_decode_attention).
#
# The grid is one program a SLOT, not one a (slot, page): a context of
# 16,384 tokens is 256 pages, and 48 x 256 grid steps a layer, nine in
# ten of them dead, cost more than the pages' read. The program walks
# the slot's LIVE pages alone, a chunk (gqa_chunk_pages) at a time,
# fetching them from the pool in HBM itself (one DMA a page into a
# double-buffered VMEM tile, the next chunk in flight while this one
# is attended), from the first page the window still touches to the
# last the length reaches. Scores are [H, keys] (heads on sublanes,
# keys on lanes) from ONE matmul of the block-diagonal query against
# the chunk: row h holds q_h in the D columns of ITS K/V head, h // G,
# and zeros elsewhere, so G query heads read each K/V head and no K/V
# row is repeated.

# A chunk is at most this many pages and at most the bytes one buffer
# of them holds on the widest grouped pool served (8 pages of 64 keys x
# 1,024 channels of bfloat16): K and V, double-buffered, are four such
# buffers of the v5e's scoped VMEM.
GQA_CHUNK_PAGES = 8
GQA_CHUNK_BYTES = 8 * 64 * 1024 * 2
GQA_KERNEL_NAME = "gqa_paged_decode"


def gqa_chunk_pages(page: int, width: int, itemsize: int,
                    table_width: int) -> int:
    """Pages a step of the kernel's inner loop attends, from the
    pool's shapes alone: GQA_CHUNK_PAGES where a buffer of them stays
    within GQA_CHUNK_BYTES (every grouped pool served: 256 to 1,024
    channels), fewer on a wider pool (an MHA pool of 4,096 channels
    walks 2 pages a chunk), never more than the table has entries and
    never less than one."""
    return max(1, min(GQA_CHUNK_PAGES, table_width,
                      GQA_CHUNK_BYTES // (page * width * itemsize)))


def window_start(lengths, window: int):
    """The first position a query at ``lengths - 1`` still sees: key j
    is visible iff j < length and (no window or j > length - 1 -
    window)."""
    if not window:
        return jnp.zeros_like(lengths)
    return jnp.maximum(lengths - window, 0)


def _group_block_mask(rows: int, heads: int, kv_heads: int,
                      depth: int, positions: int = 1):
    """[rows, Hkv*D] True where row h (a query head; rows past
    ``heads`` are padding) meets the D columns of its K/V head. With
    ``positions`` > 1 query positions a slot, row r * heads + h is
    head h of position r."""
    group = heads // kv_heads
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, kv_heads * depth), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, kv_heads * depth), 1)
    if positions == 1:
        mine = row // group
        return (row < heads) & (col >= mine * depth) & (
            col < (mine + 1) * depth)
    mine = jax.lax.rem(row, heads) // group
    return (row < positions * heads) & (col >= mine * depth) & (
        col < (mine + 1) * depth)


def _row_positions(shape: tuple, heads: int, positions: int):
    """int32 ``shape`` ([rows, n]): the query position r of row
    r * heads + h (padding rows: the last), by comparisons alone."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    out = jnp.zeros(shape, jnp.int32)
    for r in range(1, positions):
        out = out + (row >= r * heads).astype(jnp.int32)
    return out


def _gqa_paged_decode_kernel(table_ref, len_ref, next_ref, *refs,
                             page: int, chunk: int, heads: int,
                             kv_heads: int, depth: int, window: int,
                             scale: float, softmax_dtype,
                             positions: int = 1, block: int = 1,
                             halves: bool = False,
                             value_lanes: int = 0):
    """One slot: online softmax over its live pages, a chunk of
    ``chunk`` pages a step of the inner loop, its scores and running
    terms kept in ``softmax_dtype``. ``positions`` > 1: the slot's
    newest ``positions`` keys are query positions too: row
    r * heads + h is head h of the query at key position
    length - positions + r; every live page is still read once. What a
    query sees is the VISIBLE BLOCK's rule: position r sees the keys
    below length - positions + block * (r // block + 1). ``block`` 1
    is a verify block (each query the keys up to its own and, in a
    window layer, its own newest ``window``); ``block`` == positions
    a block whose queries ALL see all ``length`` keys (a block
    denoised as one: nothing to mask by row); a ``block`` between
    them (no window) is block-causal between the call's blocks.

    ``halves`` (each half of the positions whole visible blocks: two
    blocks a slot, or a control's eight causal positions): one scalar
    more rides the prefetch, behind ``next_ref``: the slot's LIVE query
    positions. A slot with no more than half of them live has a dead
    second half: its program is the call's over the first half's rows
    alone (no row of which sees a key of the second half's), and the
    second half's output is zeros.

    The hand-over between programs: k_buf, v_buf, the semaphores and
    ``carry`` (SMEM: [0] the buffer half the next seated slot's chunk
    0 arrives in, [1] whether it is in flight) persist from program to
    program. While a slot attends its LAST chunk it starts chunk 0 of
    the next seated slot (``next_ref[b]``, the batch's size if there
    is none) into the half that chunk does not hold; that slot's
    program finds ``carry[1]`` set, starts nothing and waits for the
    same copies, built from ITS table row and length on both sides
    (``copies``). Only the first seated slot of a call starts its own
    chunk 0, and the last starts nobody's: nothing is left in flight.
    A slot of length 0 (one without a request: the serving step's
    ``live`` mask, Attention._decode_attend_paged) writes its zero
    output block and touches nothing else: no DMA started, none waited
    for, ``carry`` and the semaphores as it found them, so a fetch in
    flight passes over it to the slot it is for.

    ``value_lanes`` > 0: a pool of LATENT rows (one leaf, one row a
    token for all heads: mla_paged_decode_attention_kernel): there are
    no V pages and no V buffer, every query row reads the whole row
    (``depth`` its width), and a key's value is the first
    ``value_lanes`` lanes of its own row, taken from the tile the
    scores were computed from: a page is fetched and read ONCE."""
    live_ref, refs = (refs[0], refs[1:]) if halves else (None, refs)
    if value_lanes:
        q_ref, k_hbm, o_ref, k_buf, sems, carry = refs
        v_hbm = v_buf = None
    else:
        q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, carry = refs
    b = pl.program_id(0)
    batch = pl.num_programs(0)
    rows = q_ref.shape[0]
    table_width = table_ref.shape[1]

    @pl.when(b == 0)
    def _clear():
        # a chunk's tail past the last live page is never fetched:
        # what lies there is masked by position, and must be finite
        k_buf[...] = jnp.zeros_like(k_buf)
        if v_buf is not None:
            v_buf[...] = jnp.zeros_like(v_buf)
        carry[0] = 0
        carry[1] = 0

    def live_pages(who):
        """Of slot ``who``: the lowest key ANY of its query positions
        sees (the first's), and the first and last logical page its
        keys lie in (the last read under length > 0 alone)."""
        upto = len_ref[who]
        lowest = jnp.maximum(upto - (positions - 1 + window), 0) \
            if window else 0
        return lowest, (lowest // page, (upto - 1) // page)

    length = len_ref[b]
    low, mine = live_pages(b)
    first, last = mine
    chunks = (last - first) // chunk + 1

    def copies(who, pages, c, half):
        """Chunk ``c`` of slot ``who``, whose live pages are ``pages``,
        into buffer half ``half``: the same descriptors for whoever
        starts them and whoever waits."""
        start_page, end = pages
        out = []
        for i in range(chunk):
            logical = start_page + c * chunk + i
            # a table narrower than the context is a RING: logical
            # page p lives in entry p % width
            pid = table_ref[who, jax.lax.rem(
                jnp.minimum(logical, end), table_width)]
            dst = pl.ds(i * page, page)
            pair = (pltpu.make_async_copy(
                k_hbm.at[pid], k_buf.at[half, dst], sems.at[0, half]),)
            if v_hbm is not None:
                pair += (pltpu.make_async_copy(
                    v_hbm.at[pid], v_buf.at[half, dst],
                    sems.at[1, half]),)
            out.append((logical <= end, pair))
        return out

    def start(who, pages, c, half):
        for live, pair in copies(who, pages, c, half):
            @pl.when(live)
            def _():
                for copy in pair:
                    copy.start()

    def wait(c, half):
        for live, pair in copies(b, mine, c, half):
            @pl.when(live)
            def _():
                for copy in pair:
                    copy.wait()

    @pl.when(length == 0)
    def _parked():
        # a slot without a request (the step's mask hands it length 0):
        # no page fetched, no tile through the MXU, nothing to wait for
        o_ref[...] = jnp.zeros_like(o_ref)

    def attend(count):
        """The slot's first ``count`` query rows against its live
        pages, zeros for the rows behind them."""
        base = carry[0]         # the half chunk 0 is in, or is to go in
        heir = next_ref[b]      # the next seated slot

        @pl.when(carry[1] == 0)
        def _first_seated():
            start(b, mine, 0, base)

        carry[1] = 0
        q = q_ref[...] if count == rows else q_ref[:count]   # [count, D]
        if value_lanes:
            q_bd = q            # every head reads the one latent row
        else:
            mask = _group_block_mask(count, heads, kv_heads, depth,
                                     positions)
            q_bd = jnp.where(
                mask, jnp.concatenate(
                    [q.astype(jnp.float32)] * kv_heads, axis=1),
                0.0).astype(q.dtype)

        def body(c, carried):
            o, m, l = carried
            half = jax.lax.rem(base + c, 2)

            @pl.when(c + 1 < chunks)
            def _next():
                start(b, mine, c + 1, 1 - half)

            @pl.when((c + 1 == chunks) & (heir < batch))
            def _hand_over():
                start(heir, live_pages(heir)[1], 0, 1 - half)
                carry[0] = 1 - half
                carry[1] = 1

            wait(c, half)
            tile = k_buf[half].astype(q.dtype)
            scores = jax.lax.dot_general(
                q_bd, tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            scores = kept_in(scores, softmax_dtype)  # [count, span]
            pos = (first + c * chunk) * page + \
                jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            if positions == 1 or block == positions:
                visible = (pos >= low) & (pos < length)
            else:
                # row r * heads + h: the keys below its block's end
                # (block 1: its own position + 1)
                upper = length - (positions - block)
                ahead = _row_positions(scores.shape, heads * block,
                                       positions // block)
                upper = upper + (ahead if block == 1 else ahead * block)
                visible = pos < upper
                if window:
                    visible &= pos >= upper - window
            scores = jnp.where(visible, scores, _NEG_INF)
            m_new = jnp.maximum(
                m, jnp.max(scores, axis=1, keepdims=True))
            correction = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new)
            l = l * correction + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(q.dtype), tile[:, :value_lanes] if value_lanes
                else v_buf[half].astype(q.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [count, Hkv*D]
            return (kept_in(o * correction + pv, softmax_dtype), m_new,
                    kept_in(l, softmax_dtype))

        o, _m, l = jax.lax.fori_loop(
            0, chunks, body,
            (jnp.zeros((count, value_lanes or kv_heads * depth),
                       jnp.float32),
             jnp.full((count, 1), _NEG_INF, jnp.float32),
             jnp.zeros((count, 1), jnp.float32)))
        out = o / jnp.where(l == 0.0, 1.0, l)
        if value_lanes:
            out = out.astype(o_ref.dtype)
        else:
            out = jnp.where(mask, out, 0.0)
            # each row has one live block of D columns (its K/V
            # head's): the sum over the blocks folds [count, Hkv*D]
            # into [count, D]
            out = sum(out[:, h * depth:(h + 1) * depth]
                      for h in range(kv_heads)).astype(o_ref.dtype)
        if count == rows:
            o_ref[...] = out
        else:
            o_ref[:count] = out
            o_ref[count:] = jnp.zeros_like(o_ref[count:])

    if not halves:
        pl.when(length > 0)(lambda: attend(rows))
    else:
        # all of the slot's query rows, or, its second half dead, the
        # first half alone: by the same rule, which lets no row of the
        # first half see a key of the second
        both = live_ref[b] > positions // 2
        pl.when((length > 0) & both)(lambda: attend(rows))
        pl.when((length > 0) & jnp.logical_not(both))(
            lambda: attend(rows // 2))


def next_seated(lengths):
    """[B] int32: for slot b the least b' > b with lengths[b'] > 0,
    B where there is none: whose first chunk slot b's program fetches
    behind its own last (a reverse cumulative minimum)."""
    batch = lengths.shape[0]
    index = jnp.arange(batch, dtype=jnp.int32)
    seated = jnp.where(lengths > 0, index, batch)
    after = jnp.concatenate(
        [seated[1:], jnp.full((1,), batch, jnp.int32)])
    return jax.lax.cummin(after, reverse=True)


@functools.partial(jax.jit,
                   static_argnames=("window", "softmax_dtype", "name",
                                    "block"),
                   inline=True)
def gqa_paged_decode_attention_kernel(q, k_pages, v_pages, block_table,
                                      lengths, window: int = 0,
                                      softmax_dtype=jnp.float32,
                                      name: Optional[str] =
                                      GQA_KERNEL_NAME,
                                      block: int = 0,
                                      live_positions=None):
    """Pallas path for a pool of Hkv <= H K/V heads. q: [B, S, H, D];
    k_pages/v_pages: [P, page, Hkv*D]; lengths: [B] valid-key counts
    (the S tokens written this step included). S == 1 is the decode
    step; S > 1 a call whose query r sits at key position
    length - S + r (S * H query rows a slot against each live page,
    read ONCE). ``window`` > 0: a query sees its newest ``window``
    keys alone (for S == 1 positions length - window .. length - 1),
    and no page wholly behind them is read. ``block`` is the VISIBLE
    BLOCK (S a whole number of them; no window beside one over 1):
    query r sees the keys below length - S + block * (r // block + 1).
    0 or 1: the keys up to its own (a verify block); S: every query
    all ``lengths`` keys, the call's own S included (a block denoised
    as one); between them: block-causal between the call's blocks.
    ``live_positions`` ([B] int32; each half of S whole visible
    blocks: a block pass's two blocks a slot): how many of a slot's S
    query positions anybody reads. A slot with no more than S / 2 of
    them costs what a call of its first half alone costs, and has
    zeros for its second half's rows.
    block_table: [B, T] int32, entry p % T the page of logical page p:
    a table as wide as the context is an ordinary block table, a
    narrower one a RING (its T pages hold the newest T logical pages;
    T >= ceil((window + S - 1) / page) + 1, so that no live key is
    overwritten). A slot of length 0 yields zeros. ``softmax_dtype``:
    kept_in. Returns [B, S, H, D] in q.dtype.

    Jitted INLINE: the lowered program is what the plain function
    gives, but the kernel's body (some fifty conditional DMA starts
    and waits: about a second of Python on a serving host) is traced
    ONCE for all layers of the same shapes and window and for every
    program that holds them (the cache's initialisation and the decode
    step), not once a call site a program; a program's set-up time
    would otherwise grow with its attention layers (PERF.md, PR 41)."""
    batch, seq, heads, depth = q.shape
    page, width = k_pages.shape[1], k_pages.shape[2]
    kv_heads = width // depth
    block = _visible_block(block, seq, window)
    halves = live_positions is not None
    if heads % kv_heads or kv_heads * depth != width:
        raise ValueError(
            f"{heads} query heads over a pool of {width} channels "
            f"(heads of {depth})")
    if halves and (seq % (2 * block) or seq // 2 * heads % 16):
        raise ValueError(
            f"live_positions: two halves a slot, each whole visible "
            f"blocks and whole sublane tiles of query rows ({seq} "
            f"positions, blocks of {block}, {heads} heads)")
    # whole sublane tiles of query rows (bfloat16 packs 16 a tile)
    rows = -(-seq * heads // 16) * 16
    q_rows = jnp.pad(q.reshape(batch, seq * heads, depth),
                     ((0, 0), (0, rows - seq * heads), (0, 0)))
    chunk = gqa_chunk_pages(page, width, k_pages.dtype.itemsize,
                            block_table.shape[1])
    lengths = lengths.astype(jnp.int32)
    scalars = (block_table.astype(jnp.int32), lengths,
               next_seated(lengths)) + (
        (live_positions.astype(jnp.int32),) if halves else ())
    row_spec = pl.BlockSpec((None, rows, depth),
                            lambda b, *scalars: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(batch,),
        in_specs=[row_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((2, chunk * page, width), k_pages.dtype),
            pltpu.VMEM((2, chunk * page, width), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(
            _gqa_paged_decode_kernel, page=page, chunk=chunk,
            heads=heads, kv_heads=kv_heads, depth=depth,
            window=int(window), scale=1.0 / (depth ** 0.5),
            softmax_dtype=softmax_dtype,
            **({} if seq == 1 else {"positions": seq}),
            **({} if block == 1 else {"block": block}),
            **({"halves": True} if halves else {})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, rows, depth), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=name,
    )(*scalars, q_rows, k_pages, v_pages)
    return out[:, :seq * heads].reshape(batch, seq, heads, depth)


# ---------------------------------------------------------------------
# A pool of LATENT rows (multi-head latent attention, the absorbed
# form): ONE leaf a layer, [P, page, W], a token's row the compressed
# K/V vector c (its first ``value_lanes`` lanes) followed by the one
# rotary key all heads share. A query row is a head's absorbed query
# [q_h W_uk,h^T ; q_h^R] of the same W lanes; a key's score is its dot
# with the whole row and its value the row's first ``value_lanes``
# lanes: what the caller multiplies by W_uv,h afterwards. The kernel is
# the one above with no V pages (``value_lanes``): one program a slot,
# S * H query rows against each live page, fetched and read once, the
# next seated slot's first chunk fetched behind this slot's last.

MLA_KERNEL_NAME = "mla_paged_decode"


@functools.partial(jax.jit,
                   static_argnames=("value_lanes", "scale",
                                    "softmax_dtype"),
                   inline=True)
def mla_paged_decode_attention_kernel(q, kv_pages, block_table, lengths,
                                      *, value_lanes: int, scale: float,
                                      softmax_dtype=jnp.float32):
    """Pallas path of a latent pool. q: [B, S, H, W] absorbed queries;
    kv_pages: [P, page, W]; lengths: [B] valid-key counts (the S rows
    written this step included); query r of S sits at key position
    length - S + r and sees the keys up to its own (a verify block).
    ``scale`` multiplies the scores (1 / sqrt of the EXPANDED query's
    depth: the caller's to give, no shape here says it).
    -> [B, S, H, value_lanes] in q.dtype: softmax-weighted sums of the
    rows' first ``value_lanes`` lanes, accumulated in float32 (scores
    and running terms kept in ``softmax_dtype``). A slot of length 0
    yields zeros and costs nothing. Jitted inline, as the grouped
    kernel is: one trace of the body for all layers."""
    batch, seq, heads, depth = q.shape
    page, width = kv_pages.shape[1], kv_pages.shape[2]
    if depth != width or not 0 < value_lanes <= width:
        raise ValueError(
            f"absorbed queries of {depth} lanes over latent rows of "
            f"{width} (values: the first {value_lanes})")
    rows = -(-seq * heads // 16) * 16
    q_rows = jnp.pad(q.reshape(batch, seq * heads, depth),
                     ((0, 0), (0, rows - seq * heads), (0, 0)))
    chunk = gqa_chunk_pages(page, width, kv_pages.dtype.itemsize,
                            block_table.shape[1])
    lengths = lengths.astype(jnp.int32)
    scalars = (block_table.astype(jnp.int32), lengths,
               next_seated(lengths))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(batch,),
        in_specs=[pl.BlockSpec((None, rows, depth),
                               lambda b, *scalars: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, rows, value_lanes),
                               lambda b, *scalars: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, chunk * page, width), kv_pages.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),
            pltpu.SMEM((2,), jnp.int32)],
    )
    out = pl.pallas_call(
        functools.partial(
            _gqa_paged_decode_kernel, page=page, chunk=chunk,
            heads=heads, kv_heads=1, depth=depth, window=0,
            scale=float(scale), softmax_dtype=softmax_dtype,
            positions=seq, value_lanes=int(value_lanes)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch, rows, value_lanes),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=MLA_KERNEL_NAME,
    )(*scalars, q_rows, kv_pages)
    return out[:, :seq * heads].reshape(batch, seq, heads, value_lanes)


def mla_paged_decode_attention_xla(q, kv_pages, block_table, lengths,
                                   *, value_lanes: int, scale: float,
                                   softmax_dtype=jnp.float32):
    """The kernel above as an XLA gather (the CPU/fallback road and
    the tests' oracle): every table entry's page gathered, one masked
    softmax a query position, query r of S at key position
    length - S + r seeing the keys up to its own. A slot of length 0
    yields zeros."""
    batch, seq, _heads, _depth = q.shape
    rows = kv_pages[block_table].reshape(batch, -1, kv_pages.shape[2])
    scores = jnp.einsum("bqhw,bkw->bqhk", q, rows,
                        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(rows.shape[1], dtype=jnp.int32)
    upper = lengths[:, None] - (seq - 1) + jnp.arange(
        seq, dtype=jnp.int32)[None, :]                       # [B, S]
    visible = pos[None, None, :] < upper[:, :, None]
    scores = jnp.where(visible[:, :, None],
                       kept_in(scores, softmax_dtype), _NEG_INF)
    probs = kept_in(jax.nn.softmax(scores, axis=-1), softmax_dtype)
    out = jnp.einsum("bqhk,bkw->bqhw", probs.astype(q.dtype),
                     rows[..., :value_lanes],
                     preferred_element_type=jnp.float32).astype(q.dtype)
    return _zero_where_empty(out, lengths)


def mla_paged_decode_attention(q, kv_pages, block_table, lengths, *,
                               value_lanes: int, scale: float,
                               impl: Optional[str] = None,
                               softmax_dtype=jnp.float32):
    """Dispatch of a latent pool's decode call by paged_decode_road
    (``latent``): the kernel on a TPU, the gather elsewhere."""
    road = paged_decode_road(impl, grouped=True, latent=True,
                             positions=q.shape[1])
    call = mla_paged_decode_attention_kernel if road == "mla_kernel" \
        else mla_paged_decode_attention_xla
    return call(q, kv_pages, block_table, lengths,
                value_lanes=value_lanes, scale=scale,
                softmax_dtype=softmax_dtype)


def _visible_block(block: int, seq: int, window: int) -> int:
    """A call's visible block as its mask rule reads it: 1 for a call
    of one position or with none given (each query the keys up to its
    own); else ``block``, of which ``seq`` must be a whole number and
    beside which there is no window."""
    if seq == 1 or block <= 1:
        return 1
    if seq % block:
        raise ValueError(
            f"{seq} query positions are no whole number of visible "
            f"blocks of {block}")
    if window:
        raise NotImplementedError(
            "a visible block of several positions under a window")
    return block


def paged_decode_attention_xla_windowed(q, k_pages, v_pages,
                                        block_table, lengths,
                                        window: int = 0,
                                        softmax_dtype=jnp.float32,
                                        block: int = 0,
                                        live_positions=None):
    """The kernel above as an XLA gather (the CPU/fallback path and
    the tests' oracle): every table entry's page gathered, each row's
    POSITION worked out from the entry it came through (entry c holds
    the newest logical page p <= the last with p % T == c: the ring
    rule, which for a table as wide as the context is p == c), and one
    masked softmax over the positions the window admits; query r of S
    at key position length - S + r sees the keys below
    length - S + block * (r // block + 1) (``block``: the kernel's
    visible block; 0 or 1: the keys up to its own). A slot of length 0
    yields zeros, and so do the query positions from a slot's
    ``live_positions`` on (the kernel's; any S here)."""
    batch, seq, heads, depth = q.shape
    page = k_pages.shape[1]
    entries = block_table.shape[1]
    block = _visible_block(block, seq, window)
    kv_heads = k_pages.shape[2] // depth
    k_all = k_pages[block_table].reshape(
        batch, entries * page, kv_heads, depth)
    v_all = v_pages[block_table].reshape(
        batch, entries * page, kv_heads, depth)
    last = (jnp.maximum(lengths, 1) - 1) // page            # [B]
    entry = jnp.arange(entries, dtype=jnp.int32)
    logical = last[:, None] - jnp.mod(last[:, None] - entry[None, :],
                                      entries)              # [B, T]
    pos = (logical[:, :, None] * page + jnp.arange(
        page, dtype=jnp.int32)[None, None, :]).reshape(batch, -1)
    if seq == 1:
        low = window_start(lengths, window)
        visible = (pos >= low[:, None]) & (pos < lengths[:, None]) & (
            pos >= 0)
        return _zero_where_empty(masked_attention(
            q, k_all, v_all, visible[:, None, None, :], q.dtype,
            softmax_dtype), lengths)
    # [B, S]: the keys query r sees are those below upper[:, r]
    upper = lengths[:, None] - (seq - 1)
    ends = jnp.arange(seq, dtype=jnp.int32)
    if block > 1:
        ends = (ends // block + 1) * block - 1
    upper = upper + ends[None, :]
    low = window_start(upper, window)
    pos = pos[:, None, :]
    visible = (pos >= low[:, :, None]) & (pos < upper[:, :, None]) & (
        pos >= 0)
    out = _zero_where_empty(masked_attention(
        q, k_all, v_all, visible[:, None], q.dtype, softmax_dtype),
        lengths)
    if live_positions is None:
        return out
    dead = jnp.arange(seq)[None, :] >= live_positions[:, None]
    return jnp.where(dead[:, :, None, None], jnp.zeros_like(out), out)


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


def _plain_call(grouped: bool, window: int, positions: int) -> bool:
    """An MHA pool's one-token call under no window: the one call
    whose gather is the plain one and whose int8 pages have a kernel."""
    return not (grouped or window or positions > 1)


def paged_decode_road(impl: Optional[str], *, grouped: bool,
                      window: int = 0, int8: bool = False,
                      positions: int = 1, latent: bool = False) -> str:
    """Which of the four implementations a one-token paged decode call
    runs: the dispatch below and a serving report
    (workloads/serve.paged_decode_impl) both ask here, so the report
    names what engaged. It reads the backend (through
    resolve_paged_impl), whether the pool holds fewer K/V heads than
    the query has (``grouped``), the layer's ``window`` and whether
    the pages are int8 (scales handed in), and nothing else.

    ``impl`` None is the Pallas kernel on a TPU and the XLA gather
    elsewhere, for every pool; "kernel" and "xla" pass through. WHICH
    kernel and which gather the pool, the layer and the pages decide:

        pool, layer          bf16/f32 pages           int8 pages
                             kernel      xla          kernel     xla
        MHA, no window       gqa_kernel  xla          kernel     xla
        grouped, no window   gqa_kernel  xla          (none)     xla
        any pool, a window   gqa_kernel  xla_windowed (none)     (none)

    gqa_kernel = gqa_paged_decode_attention_kernel (one program a
    slot over its live pages; an MHA pool is its case of as many K/V
    heads as query heads), kernel = paged_decode_attention_kernel (a
    program a (slot, table entry), the only one that reads scales),
    xla = paged_decode_attention_xla, xla_windowed =
    paged_decode_attention_xla_windowed. (none): the grouped kernel
    and the windowed gather read no scales. Where the gather can serve
    (a grouped int8 pool), None falls back to it on a TPU too and a
    named "kernel" raises NotImplementedError; under a window every
    int8 call does. ``positions`` > 1 (several query positions a slot:
    a verify block, or the blocks of a model with TransformerConfig.
    block_diffusion) is the last row's for any pool: gqa_kernel and
    xla_windowed alone mask by query position, by the dispatch's
    visible ``block``, and they alone take ``live_positions``.

    ``latent`` (a pool of latent rows, one leaf a layer:
    mla_paged_decode_attention) has two roads of its own at any number
    of positions, mla_kernel and mla_xla, and neither window nor int8
    pages."""
    want = resolve_paged_impl(impl)
    if latent:
        if window or int8:
            raise NotImplementedError(
                "no window and no int8 pages over a latent pool")
        return "mla_kernel" if want == "kernel" else "mla_xla"
    if _plain_call(grouped, window, positions) and (
            int8 or want == "xla"):
        return want
    if int8 and (window or impl == "kernel" or positions > 1):
        raise NotImplementedError(
            "no int8 pages under a window, the grouped kernel or a "
            "verify block")
    if want == "kernel" and not int8:
        return "gqa_kernel"
    return "xla_windowed" if window or positions > 1 else "xla"


def paged_decode_attention(q, k_pages, v_pages, block_table, lengths,
                           impl: Optional[str] = None,
                           k_scales=None, v_scales=None,
                           window: int = 0, softmax_dtype=jnp.float32,
                           block: int = 0, live_positions=None):
    """Dispatch by paged_decode_road (the selection rule's one table).
    k_scales/v_scales switch an MHA pool to its int8 kernel and the
    plain gather to int8-page dequant. ``window`` > 0: a layer that
    sees its newest ``window`` keys alone; its table may then be a
    RING narrower than the context, entry p % T the page of logical
    page p. q of S > 1 positions a slot: the last S keys are the
    queries' own, and query r sees the keys below
    length - S + block * (r // block + 1), ``block`` being the visible
    block (0 or 1: a verify block, each query the keys up to its own;
    S: a block denoised as one; between: block-causal between the
    call's blocks, of which ``live_positions`` [B] says how many of a
    slot's positions anybody reads:
    gqa_paged_decode_attention_kernel). The grouped kernel and the
    windowed gather alone keep their softmax in ``softmax_dtype``
    (kept_in).
    On every road a slot of length 0 yields zeros, and on the kernels'
    costs nothing: that is how a serving step hands over a slot
    without a request (the ``live`` mask of
    Attention._decode_attend_paged)."""
    grouped = k_pages.shape[2] != q.shape[2] * q.shape[3]
    road = paged_decode_road(
        impl, grouped=grouped, window=window,
        int8=k_scales is not None, positions=q.shape[1])
    if road == "gqa_kernel":
        # An MHA pool's one-token call goes unnamed: its device events
        # keep the name of the scope it is called in,
        # attn._decode_attend_paged in the model, which is what a
        # trace's reader knows them by.
        plain = _plain_call(grouped, window, q.shape[1])
        return gqa_paged_decode_attention_kernel(
            q, k_pages, v_pages, block_table, lengths, window=window,
            softmax_dtype=softmax_dtype,
            name=None if plain else GQA_KERNEL_NAME, block=block,
            live_positions=live_positions)
    if road == "xla_windowed":
        return paged_decode_attention_xla_windowed(
            q, k_pages, v_pages, block_table, lengths, window=window,
            softmax_dtype=softmax_dtype, block=block,
            live_positions=live_positions)
    if road == "kernel":
        return paged_decode_attention_kernel(
            q, k_pages, v_pages, block_table, lengths, k_scales,
            v_scales)
    return paged_decode_attention_xla(
        q, k_pages, v_pages, block_table, lengths, k_scales=k_scales,
        v_scales=v_scales)

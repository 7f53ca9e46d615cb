"""Grouped matmul: one matmul a group of rows, each group against its
own block of a stacked weight.

``lhs`` [m, k] holds its rows sorted by group, group g in rows
offsets[g] .. offsets[g] + sizes[g]; ``rhs`` [g, k, n] holds one
[k, n] block a group, AS STORED: the kernel picks each tile's block
straight from HBM by a scalar-prefetched group id, so no call copies,
transposes or pads the stack (the copy of a 638 MB expert stack a call
is what sank jax.lax.ragged_dot here: PERF.md, PR 31). The kernel is
JAX's own Pallas one (jax.experimental.pallas.ops.tpu.megablox.gmm);
what this module adds is the tiling for THIS use and the contract
around it.

The use: a serving prefill's routed experts (models/moe.py
grouped_experts). A few hundred to a few thousand rows meet 40 to 64
groups of 12 to 48 rows each, so a row tile nearly always straddles
group boundaries and the kernel visits (tile, group) pairs: about
``groups + m / ROW_TILE`` visits, each reading that group's [k, tn]
slab again. A visit's multiply-adds (ROW_TILE x k x tn) hide under
its slab's read while ROW_TILE stays under the chip's flops a byte
(197e12 / 819e9 = 240 on a v5e), so the call is bound by the slabs'
reads: visits / groups times the stack's own read time. Hence the
tiling: the whole contraction in one step (tk = k: a slab is one DMA
of megabytes, where the kernel's default 128 x 128 blocks are 32 KiB
a grid step and the step's overhead is the time), and the widest
column tile whose double-buffered slab fits the kernel's memory.
"""

from __future__ import annotations

import jax.numpy as jnp
# the kernel itself, not the package's custom-VJP wrapper of the
# same name: serving needs no gradient
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _gmm

# Rows a tile. Under the v5e's 240 flops a byte, so a visit stays
# bound by its weights' read; a multiple of the bfloat16 sublane tile.
ROW_TILE = 128
# A [k, tn] slab of the stack is double-buffered in the kernel's 16
# MiB of scoped VMEM beside the row tile, the output tile and the
# accumulator.
_SLAB_BYTES = 4 << 20


def tiling(k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """(tm, tk, tn) for a [*, k] x [g, k, n] call: all of k a step,
    and n in the fewest equal lane-aligned tiles whose [k, tn] slab
    stays under _SLAB_BYTES."""
    lanes = -(-n // 128)
    widest = max(1, _SLAB_BYTES // (k * itemsize * 128))
    tiles = -(-lanes // widest)
    return ROW_TILE, k, -(-lanes // tiles) * 128


def _stored_k_minor(k: int, n: int) -> bool:
    """Whether the TPU keeps a [g, k, n] array with k, not n, along
    the lanes. A device array's layout is the runtime's choice by
    SHAPE, the one with the least padding to (8, 128) tiles: a stack
    [64, 2688, 1856] (1856 = 14.5 x 128) lies in memory as
    [64, 1856, 2688]. The kernel is then handed that view and told to
    contract against its rows, and the view costs nothing; handed the
    logical shape it would have the stack copied into the default
    layout on every call (what sank jax.lax.ragged_dot here).
    tests/test_tpu_lowering.py holds the real shapes to "no copy"."""
    def padded(rows, lanes):
        return -(-rows // 8) * -(-lanes // 128)
    return padded(n, k) < padded(k, n)


def grouped_matmul(lhs, rhs, group_sizes, *, interpret: bool = False):
    """lhs [m, k] (m a multiple of ROW_TILE, rows sorted by group)
    against rhs [g, k, n], group_sizes [g] int32 with sum <= m ->
    float32 [m, n]: row r of group g is lhs[r] @ rhs[g], products
    accumulated in float32. Rows at and behind sum(group_sizes) belong
    to no group and are NEVER WRITTEN: they hold whatever the buffer
    held, NaN included, and the caller takes them out by a select. An
    empty group costs nothing. ``interpret`` runs the kernel in the
    Pallas interpreter (off the TPU)."""
    m, k = lhs.shape
    n = rhs.shape[2]
    if m % ROW_TILE:
        raise ValueError(f"{m} rows are not a multiple of {ROW_TILE}")
    k_minor = _stored_k_minor(k, n)
    return _gmm(
        lhs, jnp.swapaxes(rhs, 1, 2) if k_minor else rhs, group_sizes,
        preferred_element_type=jnp.float32,
        tiling=tiling(k, n, jnp.dtype(rhs.dtype).itemsize),
        transpose_rhs=k_minor, interpret=interpret)

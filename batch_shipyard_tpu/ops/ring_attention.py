"""Ring attention: exact attention over sequence shards via an ICI
ppermute ring.

Long-context mechanism (SURVEY.md section 5.7 net-new design space):
the sequence is sharded over the ``sp`` mesh axis; each device holds
its Q shard permanently and rotates KV shards around the ring,
accumulating exact attention with the online-softmax update from
ops/attention.py. After sp steps every Q position has attended to the
full global sequence — memory per device stays O(T/sp), and the KV
rotation (lax.ppermute, riding adjacent-neighbor ICI links) overlaps
with the per-block attention compute under XLA's scheduler.

Differentiable end-to-end (scan + ppermute have transposable rules),
so the same code path serves training — this is how the framework runs
contexts larger than one chip's HBM.

Use under shard_map with q/k/v sharded as P(('dp','fsdp'), 'sp', None,
None); models/transformer.py wires this automatically when the mesh
has sp > 1.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from batch_shipyard_tpu.ops import attention as attn_ops


RING_IMPLS = ("pallas_dma", "flash", "xla")


def resolve_ring_impl(impl: str = "auto", t_local: int = 0) -> str:
    """Resolve 'auto' to a concrete ring implementation from what the
    code can observe: 'flash' (Pallas kernels per rotation,
    lax.ppermute rotation) on a TPU backend when the local shard
    length tiles the flash blocks, else 'xla'. CPU always resolves to
    'xla' — pallas interpret mode aborts inside shard_map there.
    'pallas_dma' (flash + the async-remote-DMA KV permute) is reached
    by naming it."""
    if impl == "auto":
        if (jax.default_backend() == "tpu"
                and attn_ops.flash_shapes_ok(t_local, t_local)):
            return "flash"
        return "xla"
    if impl not in RING_IMPLS:
        raise ValueError(
            f"unknown ring attention impl {impl!r}: must be one of "
            f"{', '.join(RING_IMPLS)} or 'auto'")
    return impl


def _flash_ring_rotation(q, k_cur, v_cur, my_idx, src, causal: bool):
    """One ring rotation's partial attention with the flash kernels.

    Each rotation's masking regime is one of exactly three static
    cases — fully masked (KV from a later shard), diagonal (own
    shard: causal), fully visible (earlier shard) — selected with
    lax.switch, so the offset-free flash kernels apply unchanged and
    partials merge in logsumexp space. my_idx/src may be traced (ring
    body) or concrete (single-device virtual-shard simulation).
    """

    def masked(_q, _k, _v):
        return attn_ops.masked_attention_block(_q)

    def diagonal(_q, _k, _v):
        return attn_ops.flash_attention_with_lse(_q, _k, _v, True)

    def full(_q, _k, _v):
        return attn_ops.flash_attention_with_lse(_q, _k, _v, False)

    if not causal:
        return full(q, k_cur, v_cur)
    case = jnp.where(src > my_idx, 0,
                     jnp.where(src == my_idx, 1, 2))
    return jax.lax.switch(case, (masked, diagonal, full),
                          q, k_cur, v_cur)


def _ring_attention_local_flash(q, k, v, axis_name: str, causal: bool,
                                kv_permute: str = "ppermute",
                                mesh_axis_names=None):
    """Per-shard ring body using the Pallas flash kernels (see
    _flash_ring_rotation for the 3-case selection).

    kv_permute: 'ppermute' rotates KV shards with lax.ppermute (XLA
    schedules the transfer); 'dma' uses the async-remote-DMA Pallas
    permute kernel (ops/ring_collectives.ring_permute_pair) — the
    impl='pallas_dma' tier, TPU only.
    """
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def rotate(k_cur, v_cur):
        if kv_permute == "dma":
            from batch_shipyard_tpu.ops import ring_collectives
            return ring_collectives.ring_permute_pair(
                k_cur, v_cur, axis_name, tuple(mesh_axis_names),
                int(axis_size))
        return (jax.lax.ppermute(k_cur, axis_name, perm),
                jax.lax.ppermute(v_cur, axis_name, perm))

    @jax.checkpoint
    def step(carry, t):
        o_acc, lse_acc, k_cur, v_cur = carry
        src = (my_idx - t) % axis_size
        o_s, lse_s = _flash_ring_rotation(q, k_cur, v_cur, my_idx,
                                          src, causal)
        o_acc, lse_acc = attn_ops.merge_attention_blocks(
            o_acc, lse_acc, o_s, lse_s)
        k_nxt, v_nxt = rotate(k_cur, v_cur)
        return (o_acc, lse_acc, k_nxt, v_nxt), None

    o0, lse0 = attn_ops.masked_attention_block(q)
    (o, _lse, _, _), _ = jax.lax.scan(
        step, (o0, lse0, k, v), jnp.arange(axis_size))
    return o


def ring_attention_virtual_shards(q, k, v, sp: int, causal: bool = True):
    """Run the flash-ring algorithm — the SAME 3-case rotation +
    logsumexp merge the shard_map body uses — over sp virtual sequence
    shards on a single device.

    This exists so the flash ring path is exercisable on one real TPU
    chip (pallas interpret mode aborts inside shard_map on CPU, and
    multi-chip hardware is not always at hand): tools/tpu_checks.py
    runs it against the oracle, forward and backward, on the chip.
    """
    if q.shape[1] % sp or k.shape[1] != q.shape[1]:
        raise ValueError(
            f"sequence length {q.shape[1]} (kv {k.shape[1]}) must be "
            f"equal and divisible by sp={sp}")
    t_local = q.shape[1] // sp
    outs = []
    for my_idx in range(sp):
        q_s = jax.lax.dynamic_slice_in_dim(q, my_idx * t_local,
                                           t_local, axis=1)
        o_acc, lse_acc = attn_ops.masked_attention_block(q_s)
        for t in range(sp):
            src = (my_idx - t) % sp
            k_s = jax.lax.dynamic_slice_in_dim(k, src * t_local,
                                               t_local, axis=1)
            v_s = jax.lax.dynamic_slice_in_dim(v, src * t_local,
                                               t_local, axis=1)
            o_s, lse_s = _flash_ring_rotation(
                q_s, k_s, v_s, jnp.int32(my_idx), jnp.int32(src),
                causal)
            o_acc, lse_acc = attn_ops.merge_attention_blocks(
                o_acc, lse_acc, o_s, lse_s)
        outs.append(o_acc)
    return jnp.concatenate(outs, axis=1)


def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
    """Per-shard body (runs inside shard_map). q/k/v: [B, Tl, H, D]."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    t_local = q.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    # Rematerialize each step: without this the scan's backward saves
    # every rotation's score/probability matrices (O(T_local^2) fp32
    # per step x sp steps), defeating ring attention's O(T/sp) memory
    # promise — the entire point of sequence parallelism.
    @jax.checkpoint
    def step(carry, t):
        o, m, l, k_cur, v_cur = carry
        # After t rotations we hold the KV shard originally on
        # (my_idx - t) mod axis_size.
        src = (my_idx - t) % axis_size
        o, m, l = attn_ops.attention_block_update(
            q, k_cur, v_cur, o, m, l, causal=causal,
            q_offset=my_idx * t_local, kv_offset=src * t_local,
            scale=scale)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o, m, l, k_nxt, v_nxt), None

    o, m, l = attn_ops.attention_init(q)
    (o, m, l, _, _), _ = jax.lax.scan(
        step, (o, m, l, k, v), jnp.arange(axis_size))
    return attn_ops.attention_finalize(q, o, m, l)


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = "sp",
                   causal: bool = True,
                   batch_axes: tuple[str, ...] = ("dp", "fsdp"),
                   head_axis: str = "tp",
                   impl: str = "auto"):
    """Global-view entry: q/k/v are [B, T, H, D] global arrays; returns
    the exact attention output with T sharded over axis_name.

    impl: 'pallas_dma' (flash kernels per rotation + async-remote-DMA
    KV permute — the deepest on-chip tier), 'flash' (Pallas kernels
    per rotation, lax.ppermute rotation), 'xla' (pure-XLA online
    softmax — runs anywhere), or 'auto' (resolve_ring_impl: flash on
    a TPU backend when the shard length tiles, else xla).
    """
    t_local = q.shape[1] // mesh.shape[axis_name]
    impl = resolve_ring_impl(impl, t_local)
    if impl in ("flash", "pallas_dma") and \
            not attn_ops.flash_shapes_ok(t_local, t_local):
        raise ValueError(
            f"local shard length {t_local} does not tile the "
            f"flash blocks; use impl='xla'")
    if impl == "pallas_dma":
        body = functools.partial(
            _ring_attention_local_flash, kv_permute="dma",
            mesh_axis_names=mesh.axis_names)
    else:
        body = (_ring_attention_local_flash if impl == "flash"
                else _ring_attention_local)
    spec = P(batch_axes, axis_name, head_axis, None)
    fn = shard_map(
        functools.partial(body, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        # The online-softmax carry is initialized from constants
        # (attention_init zeros), which varying-manual-axes tracking
        # would reject against the per-step varying update.
        check_vma=False)
    return fn(q, k, v)


def ring_attention_inside_shard_map(q, k, v, axis_name: str = "sp",
                                    causal: bool = True):
    """For callers already inside a shard_map (e.g. a fully shard_mapped
    train step): per-shard inputs, per-shard output."""
    return _ring_attention_local(q, k, v, axis_name, causal)

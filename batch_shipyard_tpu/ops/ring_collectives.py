"""On-chip ring collectives: async-DMA Pallas kernels for the ICI.

The flash-ring sequence-parallel path (ops/ring_attention.py) rotates
KV shards with ``lax.ppermute`` and leaves compute/communication
overlap to XLA's scheduler. These kernels take that overlap on-chip:
``pltpu.make_async_remote_copy`` moves the neighbor transfer over the
adjacent ICI link with explicit send/recv DMA semaphores, and the
kernels are double-buffered — two communication slots alternate so the
transfer for ring step t+1 is in flight while step t's local work
(output copy-out for all-gather, the additive accumulate for
reduce-scatter) executes. A regular "capacity" semaphore handshake
releases a slot to the upstream neighbor only after it has been both
copied out and forwarded, which is what makes reusing a slot every
other step safe (the MLPerf pod-scaling recipe: overlap the ring hop
with the local compute, arxiv 1909.09756).

Three kernel families:

  - ``ring_all_gather`` / ``ring_reduce_scatter``: drop-in ring
    equivalents of ``lax.all_gather`` / ``lax.psum_scatter(tiled)``
    over one mesh axis, for shard_map callers on TPU silicon.
  - ``ring_permute_pair``: one ring rotation of a (K, V) shard pair —
    the ``impl='pallas_dma'`` tier of ring attention. custom_vjp: the
    transpose of a +1 ring shift is the -1 ring shift, so the scan'd
    ring body stays differentiable end to end.
  - ``ring_all_gather_virtual`` / ``ring_reduce_scatter_virtual``:
    the SAME step schedule executed over virtual ring members resident
    on one device, with local async DMA copies standing in for the
    remote ones. Pallas interpret mode aborts inside shard_map on CPU
    (see ring_attention.py), so these are what tier-1 exercises — and
    what tools/tpu_checks.py compiles on a single real chip to prove
    the Mosaic DMA/semaphore lowering before the multi-chip path is
    allowed on 'auto' (its check ``ring_collectives``).

Shared schedule arithmetic lives in ``ag_source_shard`` /
``rs_chunk_index`` so the real and virtual kernels cannot drift.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

# Distinct barrier-semaphore ids per collective kernel family (the
# Mosaic barrier semaphore is global per collective_id; these kernels
# never run concurrently with each other's id).
_CID_PERMUTE_FWD = 11
_CID_PERMUTE_BWD = 12
_CID_ALL_GATHER = 13
_CID_REDUCE_SCATTER = 14


# ---------------------- schedule arithmetic ---------------------------

def ag_source_shard(my_idx, step, ring: int):
    """All-gather: the shard received at ring step `step` (0-based) on
    device `my_idx` is the one originally held by this device."""
    return (my_idx - step - 1) % ring


def rs_chunk_index(my_idx, step, ring: int):
    """Reduce-scatter: the chunk whose partial arrives at device
    `my_idx` at step `step` (the device adds its local contribution
    for that chunk on receipt). Initial send (step -1) is the device's
    own chunk (my_idx - 1) % ring; after ring-1 steps the device holds
    the fully reduced chunk my_idx — the lax.psum_scatter(tiled)
    layout."""
    return (my_idx - step - 2) % ring


def _neighbor_coords(axis_name: str, mesh_axis_names, target_idx):
    """MESH-coordinate device id for a ring neighbor: the ring axis
    takes the target index, every other manual mesh axis keeps this
    device's own coordinate."""
    return tuple(
        target_idx if name == axis_name else jax.lax.axis_index(name)
        for name in mesh_axis_names)


def _neighbor_barrier(axis_name: str, mesh_axis_names, left, right):
    """Block until both ring neighbors have entered the kernel — no
    remote DMA may land in a buffer whose kernel hasn't started."""
    barrier = pltpu.get_barrier_semaphore()
    pltpu.semaphore_signal(
        barrier, 1, device_id=_neighbor_coords(
            axis_name, mesh_axis_names, left),
        device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_signal(
        barrier, 1, device_id=_neighbor_coords(
            axis_name, mesh_axis_names, right),
        device_id_type=pltpu.DeviceIdType.MESH)
    pltpu.semaphore_wait(barrier, 2)


# ---------------------- ring permute (KV rotation) --------------------

def _ring_permute_kernel(k_ref, v_ref, k_out, v_out, send_sem,
                         recv_sem, *, axis_name: str, mesh_axis_names,
                         ring: int, shift: int):
    """Send this device's K/V shard `shift` hops around the ring; the
    two transfers share the link concurrently (both DMAs in flight
    before either wait)."""
    my = jax.lax.axis_index(axis_name)
    dst = jax.lax.rem(my + shift + ring, ring)
    left = jax.lax.rem(my - 1 + ring, ring)
    right = jax.lax.rem(my + 1, ring)
    _neighbor_barrier(axis_name, mesh_axis_names, left, right)
    dst_coords = _neighbor_coords(axis_name, mesh_axis_names, dst)
    rdma_k = pltpu.make_async_remote_copy(
        src_ref=k_ref, dst_ref=k_out, send_sem=send_sem.at[0],
        recv_sem=recv_sem.at[0], device_id=dst_coords,
        device_id_type=pltpu.DeviceIdType.MESH)
    rdma_v = pltpu.make_async_remote_copy(
        src_ref=v_ref, dst_ref=v_out, send_sem=send_sem.at[1],
        recv_sem=recv_sem.at[1], device_id=dst_coords,
        device_id_type=pltpu.DeviceIdType.MESH)
    rdma_k.start()
    rdma_v.start()
    rdma_k.wait()
    rdma_v.wait()


def _ring_permute_call(k, v, axis_name: str, mesh_axis_names,
                       ring: int, shift: int, collective_id: int):
    return pl.pallas_call(
        functools.partial(
            _ring_permute_kernel, axis_name=axis_name,
            mesh_axis_names=tuple(mesh_axis_names), ring=ring,
            shift=shift),
        out_shape=(jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=pltpu.CompilerParams(
            collective_id=collective_id),
    )(k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def ring_permute_pair(k, v, axis_name: str, mesh_axis_names,
                      ring: int):
    """One +1 ring rotation of the (K, V) pair via async remote DMA —
    the pallas_dma replacement for the two lax.ppermutes in the flash
    ring body. Call inside shard_map on a TPU mesh only (gated by
    kernel_select validation; see ring_attention.resolve_ring_impl)."""
    if ring == 1:
        return k, v
    return _ring_permute_call(k, v, axis_name, mesh_axis_names, ring,
                              shift=1, collective_id=_CID_PERMUTE_FWD)


def _ring_permute_fwd(k, v, axis_name, mesh_axis_names, ring):
    return ring_permute_pair(k, v, axis_name, mesh_axis_names,
                             ring), None


def _ring_permute_bwd(axis_name, mesh_axis_names, ring, _res, grads):
    g_k, g_v = grads
    if ring == 1:
        return g_k, g_v
    # Transpose of the +1 shift: cotangents travel one hop the other
    # way (y_i = x_{i-1}  =>  dx_j = dy_{j+1}).
    return _ring_permute_call(g_k, g_v, axis_name, mesh_axis_names,
                              ring, shift=-1,
                              collective_id=_CID_PERMUTE_BWD)


ring_permute_pair.defvjp(_ring_permute_fwd, _ring_permute_bwd)


# ---------------------- ring all-gather -------------------------------

def _ring_all_gather_kernel(x_ref, o_ref, comm_ref, send_sem,
                            recv_sem, local_sem, capacity_sem, *,
                            axis_name: str, mesh_axis_names,
                            ring: int):
    """Per-device body: forward the chunk received at step t-1 while
    step t's send/recv DMAs are in flight (double-buffered slots s/r),
    releasing each slot to the upstream neighbor via capacity_sem only
    once it is copied out AND resent."""
    chunk = x_ref.shape[0]
    my = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my + 1, ring)
    left = jax.lax.rem(my - 1 + ring, ring)
    # Own shard -> its output row and the first send slot.
    cp_out = pltpu.make_async_copy(
        x_ref, o_ref.at[pl.ds(my * chunk, chunk)], local_sem)
    cp_out.start()
    cp_seed = pltpu.make_async_copy(x_ref, comm_ref.at[0],
                                    recv_sem.at[0])
    cp_seed.start()
    cp_out.wait()
    cp_seed.wait()
    _neighbor_barrier(axis_name, mesh_axis_names, left, right)
    left_coords = _neighbor_coords(axis_name, mesh_axis_names, left)
    right_coords = _neighbor_coords(axis_name, mesh_axis_names, right)
    for step in range(ring - 1):
        slot, nxt = step % 2, (step + 1) % 2
        if step > 0:
            # The right neighbor freed the slot we are about to
            # overwrite on it (copied out + resent).
            pltpu.semaphore_wait(capacity_sem, 1)
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_ref.at[slot], dst_ref=comm_ref.at[nxt],
            send_sem=send_sem.at[slot], recv_sem=recv_sem.at[nxt],
            device_id=right_coords,
            device_id_type=pltpu.DeviceIdType.MESH)
        rdma.start()
        if step > 0:
            # Overlap: while the step-t transfer flies, copy the chunk
            # received at step t-1 (sitting in `slot`, which the send
            # DMA is only READING) into its output row.
            src = ag_source_shard(my, step - 1, ring)
            cp = pltpu.make_async_copy(
                comm_ref.at[slot],
                o_ref.at[pl.ds(src * chunk, chunk)], local_sem)
            cp.start()
            cp.wait()
        rdma.wait()
        if step < ring - 2:
            pltpu.semaphore_signal(
                capacity_sem, 1, device_id=left_coords,
                device_id_type=pltpu.DeviceIdType.MESH)
    src = ag_source_shard(my, ring - 2, ring)
    cp = pltpu.make_async_copy(
        comm_ref.at[(ring - 1) % 2],
        o_ref.at[pl.ds(src * chunk, chunk)], local_sem)
    cp.start()
    cp.wait()


def _ring_all_gather_local(x, *, axis_name: str, mesh_axis_names,
                           ring: int):
    chunk = x.shape[0]
    out, _comm = pl.pallas_call(
        functools.partial(
            _ring_all_gather_kernel, axis_name=axis_name,
            mesh_axis_names=tuple(mesh_axis_names), ring=ring),
        out_shape=(
            jax.ShapeDtypeStruct((ring * chunk,) + x.shape[1:],
                                 x.dtype),
            jax.ShapeDtypeStruct((2, chunk) + x.shape[1:], x.dtype),
        ),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.REGULAR],
        compiler_params=pltpu.CompilerParams(
            collective_id=_CID_ALL_GATHER),
    )(x)
    return out


def ring_all_gather(x, mesh: Mesh, axis_name: str = "sp"):
    """lax.all_gather equivalent over `axis_name` via the async-DMA
    ring kernel. x: global array with dim 0 sharded over the axis;
    returns the gathered (replicated) global array — numerically the
    identity on x, which is exactly what the parity check exploits."""
    ring = mesh.shape[axis_name]
    body = functools.partial(
        _ring_all_gather_local, axis_name=axis_name,
        mesh_axis_names=mesh.axis_names, ring=ring)
    fn = shard_map(body, mesh=mesh, in_specs=P(axis_name),
                   out_specs=P(None), check_vma=False)
    return fn(x)


# ---------------------- ring reduce-scatter ---------------------------

def _ring_reduce_scatter_kernel(x_ref, o_ref, comm_ref, send_sem,
                                recv_sem, local_sem, capacity_sem,
                                acc_vmem, add_vmem, *,
                                axis_name: str, mesh_axis_names,
                                ring: int, chunk: int):
    """Per-device body: each step forwards the partial for one chunk
    and folds the local contribution into the arriving partial. The
    additive accumulate runs in VMEM while this device's own send DMA
    is still in flight (wait_recv before the add, wait_send after)."""
    my = jax.lax.axis_index(axis_name)
    right = jax.lax.rem(my + 1, ring)
    left = jax.lax.rem(my - 1 + ring, ring)
    # Seed slot 0 with the local chunk this device forwards first.
    c0 = rs_chunk_index(my, -1, ring)
    cp = pltpu.make_async_copy(
        x_ref.at[pl.ds(c0 * chunk, chunk)], comm_ref.at[0],
        local_sem)
    cp.start()
    cp.wait()
    _neighbor_barrier(axis_name, mesh_axis_names, left, right)
    left_coords = _neighbor_coords(axis_name, mesh_axis_names, left)
    right_coords = _neighbor_coords(axis_name, mesh_axis_names, right)
    for step in range(ring - 1):
        slot, nxt = step % 2, (step + 1) % 2
        if step > 0:
            pltpu.semaphore_wait(capacity_sem, 1)
        rdma = pltpu.make_async_remote_copy(
            src_ref=comm_ref.at[slot], dst_ref=comm_ref.at[nxt],
            send_sem=send_sem.at[slot], recv_sem=recv_sem.at[nxt],
            device_id=right_coords,
            device_id_type=pltpu.DeviceIdType.MESH)
        rdma.start()
        # Prefetch the local contribution for the incoming partial
        # while both ring DMAs fly.
        c = rs_chunk_index(my, step, ring)
        cp_local = pltpu.make_async_copy(
            x_ref.at[pl.ds(c * chunk, chunk)], add_vmem, local_sem)
        cp_local.start()
        rdma.wait_recv()
        cp_recv = pltpu.make_async_copy(comm_ref.at[nxt], acc_vmem,
                                        local_sem)
        cp_local.wait()
        cp_recv.start()
        cp_recv.wait()
        # The add overlaps this device's own send (waited below).
        acc_vmem[...] = acc_vmem[...] + add_vmem[...]
        if step < ring - 2:
            cp_back = pltpu.make_async_copy(acc_vmem,
                                            comm_ref.at[nxt],
                                            local_sem)
        else:
            cp_back = pltpu.make_async_copy(acc_vmem, o_ref,
                                            local_sem)
        cp_back.start()
        cp_back.wait()
        rdma.wait_send()
        if step < ring - 2:
            pltpu.semaphore_signal(
                capacity_sem, 1, device_id=left_coords,
                device_id_type=pltpu.DeviceIdType.MESH)


def _ring_reduce_scatter_local(x, *, axis_name: str, mesh_axis_names,
                               ring: int):
    if x.shape[0] % ring:
        raise ValueError(
            f"reduce-scatter dim 0 ({x.shape[0]}) must be divisible "
            f"by the ring size {ring}")
    chunk = x.shape[0] // ring
    out, _comm = pl.pallas_call(
        functools.partial(
            _ring_reduce_scatter_kernel, axis_name=axis_name,
            mesh_axis_names=tuple(mesh_axis_names), ring=ring,
            chunk=chunk),
        out_shape=(
            jax.ShapeDtypeStruct((chunk,) + x.shape[1:], x.dtype),
            jax.ShapeDtypeStruct((2, chunk) + x.shape[1:], x.dtype),
        ),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)),
        scratch_shapes=[pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA,
                        pltpu.SemaphoreType.REGULAR,
                        pltpu.VMEM((chunk,) + x.shape[1:], x.dtype),
                        pltpu.VMEM((chunk,) + x.shape[1:], x.dtype)],
        compiler_params=pltpu.CompilerParams(
            collective_id=_CID_REDUCE_SCATTER),
    )(x)
    return out


def ring_reduce_scatter(x, mesh: Mesh, axis_name: str = "sp"):
    """lax.psum_scatter(tiled) equivalent: x global [ring, ring*chunk,
    ...] with dim 0 sharded over the axis (each device contributes one
    full row); returns the global [ring*chunk, ...] reduced-scattered
    result, i.e. jnp.sum(x, axis=0)."""
    ring = mesh.shape[axis_name]
    body = functools.partial(
        _ring_reduce_scatter_local, axis_name=axis_name,
        mesh_axis_names=mesh.axis_names, ring=ring)

    def per_device(x_local):
        return body(x_local[0])

    fn = shard_map(per_device, mesh=mesh,
                   in_specs=P(axis_name, None),
                   out_specs=P(axis_name), check_vma=False)
    return fn(x)


# ---------------------- virtual (single-device) rings -----------------

def _virtual_all_gather_kernel(x_ref, o_ref, comm_ref, sems, *,
                               ring: int):
    """All virtual ring members resident on one device: identical slot
    schedule, with local async DMA copies standing in for the remote
    ones (every per-step transfer is started before any is waited,
    and the previous step's chunk is copied out while they fly)."""
    chunk = x_ref.shape[1]
    for i in range(ring):
        o_ref[i, pl.ds(i * chunk, chunk), :] = x_ref[i]
        comm_ref[i, 0] = x_ref[i]
    for step in range(ring - 1):
        slot, nxt = step % 2, (step + 1) % 2
        dmas = [pltpu.make_async_copy(
            comm_ref.at[i, slot],
            comm_ref.at[(i + 1) % ring, nxt],
            sems.at[(i + 1) % ring]) for i in range(ring)]
        for dma in dmas:
            dma.start()
        if step > 0:
            for i in range(ring):
                src = ag_source_shard(i, step - 1, ring)
                o_ref[i, pl.ds(src * chunk, chunk), :] = (
                    comm_ref[i, slot])
        for dma in dmas:
            dma.wait()
    for i in range(ring):
        src = ag_source_shard(i, ring - 2, ring)
        o_ref[i, pl.ds(src * chunk, chunk), :] = (
            comm_ref[i, (ring - 1) % 2])


def ring_all_gather_virtual(x_shards, interpret: bool = False):
    """Run the ring all-gather schedule over `ring` virtual members on
    ONE device. x_shards: [ring, chunk, feat]; returns [ring,
    ring*chunk, feat] where row i is what ring member i would hold —
    every row must equal the concatenation of the shards."""
    ring, chunk = x_shards.shape[0], x_shards.shape[1]
    if ring < 2:
        raise ValueError(f"virtual ring needs >= 2 members, got {ring}")
    return pl.pallas_call(
        functools.partial(_virtual_all_gather_kernel, ring=ring),
        out_shape=jax.ShapeDtypeStruct(
            (ring, ring * chunk) + x_shards.shape[2:], x_shards.dtype),
        scratch_shapes=[
            pltpu.VMEM((ring, 2, chunk) + x_shards.shape[2:],
                       x_shards.dtype),
            pltpu.SemaphoreType.DMA((ring,))],
        interpret=interpret,
    )(x_shards)


def _virtual_reduce_scatter_kernel(x_ref, o_ref, comm_ref, sems, *,
                                   ring: int):
    chunk = x_ref.shape[1] // ring
    for i in range(ring):
        c0 = rs_chunk_index(i, -1, ring)
        comm_ref[i, 0] = x_ref[i, pl.ds(c0 * chunk, chunk), :]
    for step in range(ring - 1):
        slot, nxt = step % 2, (step + 1) % 2
        dmas = [pltpu.make_async_copy(
            comm_ref.at[i, slot],
            comm_ref.at[(i + 1) % ring, nxt],
            sems.at[(i + 1) % ring]) for i in range(ring)]
        for dma in dmas:
            dma.start()
        for dma in dmas:
            dma.wait()
        for i in range(ring):
            c = rs_chunk_index(i, step, ring)
            comm_ref[i, nxt] = (comm_ref[i, nxt] +
                                x_ref[i, pl.ds(c * chunk, chunk), :])
    for i in range(ring):
        o_ref[i] = comm_ref[i, (ring - 1) % 2]


def ring_reduce_scatter_virtual(x_rows, interpret: bool = False):
    """Run the ring reduce-scatter schedule over `ring` virtual
    members on ONE device. x_rows: [ring, ring*chunk, feat] (row i is
    member i's full contribution); returns [ring, chunk, feat] where
    row i is member i's reduced chunk — concatenated over i this is
    jnp.sum(x_rows, axis=0), the psum_scatter(tiled) result."""
    ring = x_rows.shape[0]
    if ring < 2:
        raise ValueError(f"virtual ring needs >= 2 members, got {ring}")
    if x_rows.shape[1] % ring:
        raise ValueError(
            f"row length {x_rows.shape[1]} must be divisible by the "
            f"ring size {ring}")
    chunk = x_rows.shape[1] // ring
    return pl.pallas_call(
        functools.partial(_virtual_reduce_scatter_kernel, ring=ring),
        out_shape=jax.ShapeDtypeStruct(
            (ring, chunk) + x_rows.shape[2:], x_rows.dtype),
        scratch_shapes=[
            pltpu.VMEM((ring, 2, chunk) + x_rows.shape[2:],
                       x_rows.dtype),
            pltpu.SemaphoreType.DMA((ring,))],
        interpret=interpret,
    )(x_rows)

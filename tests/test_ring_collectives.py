"""Ring-collective kernel correctness (ops/ring_collectives.py).

Pallas interpret mode aborts inside shard_map on CPU (see
ring_attention.py), so — exactly like the flash-ring tests — the
kernels are exercised single-device/virtual-shard style: the virtual
ring kernels run the SAME double-buffered slot schedule the
remote-DMA kernels use (shared via ag_source_shard / rs_chunk_index)
with local async DMA copies standing in for the remote ones, and are
checked against the jax.lax collectives running over the virtual
8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from batch_shipyard_tpu.ops import ring_attention, ring_collectives as rc
from batch_shipyard_tpu.parallel import mesh as mesh_mod


def _shards(ring, chunk, feat, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(ring, chunk, feat), jnp.float32)


# ---------------- schedule arithmetic ---------------------------------

def test_all_gather_schedule_covers_every_shard():
    """Over ring-1 steps plus the local shard, every device sees every
    source exactly once — the invariant the output copies rely on."""
    for ring in (2, 3, 4, 8):
        for me in range(ring):
            seen = {me} | {rc.ag_source_shard(me, t, ring)
                           for t in range(ring - 1)}
            assert seen == set(range(ring))


def test_reduce_scatter_schedule_lands_own_chunk():
    """The partial chain for chunk c starts at device c+1 and, after
    ring-1 forwarding hops, lands on device c fully reduced — the
    psum_scatter(tiled) layout."""
    for ring in (2, 3, 4, 8):
        for me in range(ring):
            # Chunk received at the last step is this device's own.
            assert rc.rs_chunk_index(me, ring - 2, ring) == me
            # Each step touches a distinct chunk.
            chunks = {rc.rs_chunk_index(me, t, ring)
                      for t in range(-1, ring - 1)}
            assert chunks == set(range(ring))


# ---------------- virtual kernels vs jax.lax references ---------------

@pytest.mark.parametrize("ring", [2, 4, 8])
def test_virtual_all_gather_matches_lax(ring):
    x = _shards(ring, 16, 128)
    got = rc.ring_all_gather_virtual(x, interpret=True)
    # jax.lax reference over the CPU mesh: gather the same shards.
    mesh = mesh_mod.make_mesh(mesh_mod.auto_axis_sizes(8, sp=ring),
                              devices=jax.devices()[:8])
    ref = shard_map(
        lambda s: jax.lax.all_gather(s[0], "sp", tiled=True),
        mesh=mesh, in_specs=P("sp"), out_specs=P(None),
        check_vma=False)(x)
    assert got.shape == (ring, ring * 16, 128)
    for i in range(ring):
        np.testing.assert_allclose(np.asarray(got[i]),
                                   np.asarray(ref), atol=1e-6)


@pytest.mark.parametrize("ring", [2, 4, 8])
def test_virtual_reduce_scatter_matches_lax(ring):
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(ring, ring * 16, 128), jnp.float32)
    got = rc.ring_reduce_scatter_virtual(x, interpret=True)
    mesh = mesh_mod.make_mesh(mesh_mod.auto_axis_sizes(8, sp=ring),
                              devices=jax.devices()[:8])
    ref = shard_map(
        lambda s: jax.lax.psum_scatter(s[0], "sp", tiled=True),
        mesh=mesh, in_specs=P("sp", None), out_specs=P("sp"),
        check_vma=False)(x)
    got_flat = got.reshape(ring * 16, 128)
    np.testing.assert_allclose(np.asarray(got_flat), np.asarray(ref),
                               atol=1e-4, rtol=1e-5)
    rel = (np.linalg.norm(np.asarray(got_flat) - np.asarray(ref)) /
           np.linalg.norm(np.asarray(ref)))
    assert rel < 1e-6, rel


def test_virtual_kernels_reject_trivial_ring():
    with pytest.raises(ValueError):
        rc.ring_all_gather_virtual(_shards(1, 16, 128))
    with pytest.raises(ValueError):
        rc.ring_reduce_scatter_virtual(_shards(1, 16, 128))
    with pytest.raises(ValueError):
        # Row length must divide the ring.
        rc.ring_reduce_scatter_virtual(_shards(4, 18, 128))


def test_virtual_all_gather_non_contiguous_values():
    """Chunk identity, not just sums: each gathered position holds the
    exact source shard (catches slot-arithmetic off-by-ones that a
    symmetric random test could mask)."""
    ring, chunk, feat = 4, 8, 128
    x = jnp.stack([jnp.full((chunk, feat), float(i + 1))
                   for i in range(ring)])
    got = rc.ring_all_gather_virtual(x, interpret=True)
    for i in range(ring):
        for src in range(ring):
            block = np.asarray(
                got[i, src * chunk:(src + 1) * chunk])
            assert (block == src + 1).all(), (i, src)


# ---------------- pallas_dma tier resolution --------------------------

def test_pallas_dma_is_reached_only_by_name(monkeypatch):
    """auto never picks the remote-DMA tier — not on cpu, and not on
    a (simulated) TPU backend; naming it passes through."""
    assert ring_attention.resolve_ring_impl("pallas_dma") == \
        "pallas_dma"
    assert ring_attention.resolve_ring_impl("auto", 1024) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ring_attention.resolve_ring_impl("auto", 1024) == "flash"

"""Collective microbench sanity on the virtual CPU mesh (the mpiBench
recipe analog must run anywhere)."""

import jax
import jax.numpy as jnp

from batch_shipyard_tpu.ops import collectives
from batch_shipyard_tpu.parallel import mesh as mesh_mod


def test_collective_bench_runs_all_ops():
    mesh = mesh_mod.make_mesh(mesh_mod.auto_axis_sizes(8))
    rows = collectives.run_collective_bench(
        mesh, axis="dp", sizes_bytes=(1 << 12,), dtype=jnp.float32)
    ops = {r["op"] for r in rows}
    assert ops == {"psum", "all_gather", "ppermute", "reduce_scatter"}
    for row in rows:
        assert row["seconds"] > 0
        assert row["algo_bw_gbps"] > 0


def test_collective_correctness():
    """The timed functions must also be *correct* collectives."""
    import numpy as np
    mesh = mesh_mod.make_mesh(mesh_mod.auto_axis_sizes(8))
    x = jnp.arange(8 * 128, dtype=jnp.float32)
    psum_fn = collectives._collective_fn(mesh, "dp", "psum")
    out = psum_fn(x)
    # Each shard contributes its slice; psum over 8 shards of the
    # sharded input returns sum of shards, replicated.
    expected = np.asarray(x).reshape(8, 128).sum(axis=0)
    np.testing.assert_allclose(np.asarray(out), expected)


def test_hierarchical_all_to_all_matches_transpose():
    """Two-phase (ICI then DCN) all-to-all delivers exactly the
    (src <-> dst) transpose a flat all-to-all would, on a factored
    2 x 4 expert mesh."""
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    n_out, n_in, d = 2, 4, 8
    devices = np.array(jax.devices()[:n_out * n_in]).reshape(
        n_out, n_in)
    mesh = Mesh(devices, ("ep_out", "ep_in"))
    rng = np.random.RandomState(0)
    # X[src_o, src_i, dst_o, dst_i, :] = the block (src -> dst).
    x_global = jnp.asarray(
        rng.randn(n_out, n_in, n_out, n_in, d), jnp.float32)

    def body(x_block):
        # per-device block [1, 1, n_out, n_in, d] -> dest-indexed.
        y = collectives.hierarchical_all_to_all(
            x_block[0, 0], "ep_out", "ep_in")
        return y[None, None]

    fn = shard_map(
        body, mesh=mesh,
        in_specs=P("ep_out", "ep_in", None, None, None),
        out_specs=P("ep_out", "ep_in", None, None, None),
        check_vma=False)
    got = np.asarray(fn(x_global))
    # Device (o, i) must end with Y[s_o, s_i] = X[s_o, s_i, o, i].
    want = np.asarray(x_global).transpose(2, 3, 0, 1, 4)
    np.testing.assert_allclose(got, want)


def test_hierarchical_all_to_all_roundtrip():
    """Applying the exchange twice returns the original blocks (the
    transpose is an involution) — the combine path of MoE dispatch."""
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    n_out, n_in, d = 2, 4, 4
    devices = np.array(jax.devices()[:n_out * n_in]).reshape(
        n_out, n_in)
    mesh = Mesh(devices, ("ep_out", "ep_in"))
    rng = np.random.RandomState(1)
    x_global = jnp.asarray(
        rng.randn(n_out, n_in, n_out, n_in, d), jnp.float32)

    def body(x_block):
        y = collectives.hierarchical_all_to_all(
            x_block[0, 0], "ep_out", "ep_in")
        z = collectives.hierarchical_all_to_all(y, "ep_out", "ep_in")
        return z[None, None]

    fn = shard_map(
        body, mesh=mesh,
        in_specs=P("ep_out", "ep_in", None, None, None),
        out_specs=P("ep_out", "ep_in", None, None, None),
        check_vma=False)
    np.testing.assert_allclose(np.asarray(fn(x_global)),
                               np.asarray(x_global))

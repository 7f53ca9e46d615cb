"""Point-to-point latency/bandwidth microbenchmark: the OSU
micro-benchmarks (osu_latency / osu_bw) analog for the TPU fabric.

Reference analog: the OSU-flavored MPI recipes
(`/root/reference/recipes/` mpiBench/IntelMPI PingPong lineage) measure
point-to-point latency and bandwidth over Infiniband. On TPU the
point-to-point primitive is `lax.ppermute` over an ICI ring: a
ping-pong is one hop to the right neighbor and one hop back, timed
over a message-size sweep — small sizes expose per-hop latency, large
sizes asymptote to per-link bandwidth.

Usage (recipe command):
    python -m batch_shipyard_tpu.workloads.p2p_bench \
        --sizes 256,4096,65536,1048576,16777216 --iters 50
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def p2p_pingpong(mesh: Mesh, axis: str, size_bytes: int,
                 iters: int = 50, dtype=jnp.bfloat16) -> dict:
    """Time a neighbor ping-pong (right hop + back) of size_bytes per
    device over the mesh axis. Returns {size_bytes, avg_pingpong_us,
    half_roundtrip_us, bus_gbps}."""
    n = mesh.shape[axis]
    if n < 2:
        raise ValueError(f"p2p needs >= 2 devices on axis {axis!r}")
    elems = max(size_bytes // jnp.dtype(dtype).itemsize, 1)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [((i + 1) % n, i) for i in range(n)]

    def body(x):
        # Chained ping-pong: the return hop depends on the outgoing
        # one, so XLA cannot elide or overlap them away; +1.0 defeats
        # common-subexpression reuse across iterations inside jit.
        y = jax.lax.ppermute(x, axis, fwd)
        return jax.lax.ppermute(y, axis, bwd) + 1.0

    spec = P(axis)
    fn = jax.jit(shard_map(
        body, mesh=mesh, in_specs=spec, out_specs=spec))
    x = jnp.ones((n, elems), dtype)
    x = fn(x)  # compile + warm
    x.block_until_ready()
    start = time.perf_counter()
    for _ in range(iters):
        x = fn(x)
    x.block_until_ready()
    elapsed = time.perf_counter() - start
    pingpong_s = elapsed / iters
    payload = elems * jnp.dtype(dtype).itemsize
    return {
        "op": "pingpong", "size_bytes": int(payload),
        "avg_pingpong_us": pingpong_s * 1e6,
        "half_roundtrip_us": pingpong_s * 1e6 / 2.0,
        # Two hops move the payload twice per iteration.
        "bus_gbps": 2.0 * payload / pingpong_s / 1e9,
    }


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--sizes", default="256,4096,65536,1048576,16777216",
        help="comma-separated per-device message sizes in bytes")
    parser.add_argument("--iters", type=int, default=50)
    parser.add_argument("--dtype", default="bfloat16")
    args = parser.parse_args()

    from batch_shipyard_tpu.parallel import mesh as mesh_mod
    from batch_shipyard_tpu.workloads import distributed

    ctx = distributed.setup()
    n_dev = jax.device_count()
    if n_dev < 2:
        distributed.log(ctx, "single device: p2p bench needs >= 2")
        return 0
    mesh = mesh_mod.make_mesh(mesh_mod.auto_axis_sizes(n_dev))
    for size in (int(s) for s in args.sizes.split(",")):
        row = p2p_pingpong(mesh, "dp", size, iters=args.iters,
                           dtype=getattr(jnp, args.dtype))
        if jax.process_index() == 0:
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

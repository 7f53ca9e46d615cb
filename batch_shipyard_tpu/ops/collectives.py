"""Collective microbenchmarks: the mpiBench/OSU recipe analog.

The reference ships MPI microbenchmark recipes (mpiBench-OpenMPI, OSU)
that exercise the Infiniband fabric; on TPU the fabric is ICI/DCN and
the collectives are XLA's (psum, all_gather, ppermute, reduce_scatter)
reached through shard_map. These functions time them per message size
and report bus bandwidth, runnable identically on a real pod slice or
the virtual CPU mesh.
"""

from __future__ import annotations

import functools
import time
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def _timeit(fn: Callable, arg, warmup: int = 2, iters: int = 10) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(arg))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(arg)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters


def _collective_fn(mesh: Mesh, axis: str, op: str) -> Callable:
    if op == "psum":
        def inner(x):
            return jax.lax.psum(x, axis)
    elif op == "all_gather":
        def inner(x):
            return jax.lax.all_gather(x, axis)
    elif op == "reduce_scatter":
        def inner(x):
            return jax.lax.psum_scatter(x, axis, tiled=True)
    elif op == "ppermute":
        size = mesh.shape[axis]

        def inner(x):
            return jax.lax.ppermute(
                x, axis, [(i, (i + 1) % size) for i in range(size)])
    else:
        raise ValueError(f"unknown collective {op!r}")
    # (in_spec, out_spec) per op: inputs are sharded over the axis;
    # psum and all_gather produce replicated outputs.
    specs = {
        "psum": (P(axis), P(None)),
        "all_gather": (P(axis), P(None)),
        "reduce_scatter": (P(axis), P(axis)),
        "ppermute": (P(axis), P(axis)),
    }
    in_spec, out_spec = specs[op]
    return jax.jit(shard_map(inner, mesh=mesh, in_specs=in_spec,
                             out_specs=out_spec, check_vma=False))


def run_collective_bench(
        mesh: Mesh, axis: str = "dp",
        ops: Iterable[str] = ("psum", "all_gather", "ppermute",
                              "reduce_scatter"),
        sizes_bytes: Iterable[int] = (1 << 16, 1 << 20, 1 << 24),
        dtype=jnp.bfloat16) -> list[dict]:
    """Time each collective per message size; returns rows of
    {op, bytes, seconds, algo_bw_gbps, bus_bw_gbps}."""
    n = mesh.shape[axis]
    results = []
    itemsize = jnp.dtype(dtype).itemsize
    for op in ops:
        fn = _collective_fn(mesh, axis, op)
        for size in sizes_bytes:
            elems = max(n * 128, size // itemsize)
            elems -= elems % (n * 128)
            x = jnp.ones((elems,), dtype=dtype)
            seconds = _timeit(fn, x)
            nbytes = elems * itemsize
            algo_bw = nbytes / seconds / 1e9
            # Bus-bandwidth correction factors (NCCL-tests convention).
            if op == "psum":
                factor = 2 * (n - 1) / n
            elif op in ("all_gather", "reduce_scatter"):
                factor = (n - 1) / n
            else:
                factor = 1.0
            results.append({
                "op": op, "bytes": nbytes, "seconds": seconds,
                "algo_bw_gbps": algo_bw,
                "bus_bw_gbps": algo_bw * factor,
            })
    return results


@functools.partial(jax.jit, static_argnames=("axis",))
def psum_latency_probe(x, axis: str = "dp"):
    """Minimal-size psum for latency measurement (OSU latency analog).
    Call under shard_map or pjit with x sharded over axis."""
    return jax.lax.psum(x, axis)


def hierarchical_all_to_all(x, outer_axis: str, inner_axis: str):
    """Two-phase all-to-all over a factored device axis: ICI first,
    then DCN — the expert-parallel dispatch primitive when experts
    span slices.

    Call inside shard_map on a mesh where the expert axis is factored
    as (outer_axis, inner_axis) — outer across slices (DCN), inner
    within a slice (ICI). ``x`` is DESTINATION-indexed per device:
    shape [n_out, n_in, ...] where x[o', i'] is the block this device
    sends to device (o', i'). Returns the SOURCE-indexed gather:
    y[o, i] = block sent to this device by device (o, i).

    Why not one all_to_all over the combined axis: that sends each
    (src, dst) block as its own DCN message — n_in^2 small messages
    per slice pair. Phase 1 (inner axis, ICI) routes blocks to the
    slice-mate whose inner rank matches the destination's; phase 2
    (outer axis, DCN) then moves ONE aggregated [n_in, ...] message
    per slice pair — n_in-fold fewer, n_in-fold bigger DCN transfers,
    which is the win on a latency-dominated cross-slice fabric.

    Phase algebra (device (o, i), A = phase-1 result, B = result):
      A[d_o, s_i] = x_{(o, s_i)}[d_o, i]      (a2a over inner, dim 1)
      B[s_o, s_i] = A_{(s_o, i)}[o, s_i]
                  = x_{(s_o, s_i)}[o, i]      (a2a over outer, dim 0)

    Reference analog: none (SURVEY.md 5.8 net-new); the factored
    exchange follows the standard hierarchical/2D all-to-all scheme
    used by MoE systems (PAPERS.md).
    """
    x = jax.lax.all_to_all(x, inner_axis, split_axis=1,
                           concat_axis=1)
    return jax.lax.all_to_all(x, outer_axis, split_axis=0,
                              concat_axis=0)

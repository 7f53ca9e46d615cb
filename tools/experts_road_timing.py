#!/usr/bin/env python3
"""The two roads of moe.RoutedExperts' routed sum, timed on the chip by
row count at the shapes of the two hybrid configurations: what
moe.GROUPED_FROM_ROWS was fixed from (PERF.md, PR 37). Not part of the
benchmark. For each shape and each row count: dense_experts and
grouped_experts (ms a call, the mean of ``--calls`` back to back), the
grouped road's kernel calls alone, each beside the time the held
stacks' own read takes at the chip's 819 GB/s; the largest difference
of the two roads' results; how the device stores the stacks.

    chiprun -- python3 tools/experts_road_timing.py [--row-tiles 128,256]

A chip run only: on another backend it says so and exits 2 (a CPU
timing of a TPU kernel's interpreter is no number)."""
import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from batch_shipyard_tpu.models import moe  # noqa: E402
from batch_shipyard_tpu.ops import grouped_matmul as gm  # noqa: E402

HBM_BYTES_PER_S = 819e9     # TPU v5e (benchmark/peaks.json)
# (d_model, d_expert, held, router outputs, top k, gated)
SHAPES = {"nemotron3nano": (2688, 1856, 64, 128, 6, False),
          "solaropen2": (4096, 1280, 40, 320, 8, True)}
ROWS = (64, 128, 256, 384, 512, 1024)


def timed(fn, args, calls: int) -> float:
    """ms a call: the mean of ``calls`` dispatched back to back."""
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = None
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--calls", type=int, default=20)
    parser.add_argument("--row-tiles", default=str(gm.ROW_TILE))
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no chip here ({device.platform}): nothing timed")
        return 2
    print(f"device {device.device_kind} x{jax.device_count()}")
    gm_row_tile = gm.ROW_TILE     # the dense road is timed once
    for name, (d, f, held, outputs, top_k, gated) in SHAPES.items():
        key = jax.random.PRNGKey(args.seed)
        keys = jax.random.split(key, 6)

        def stack(k, shape):
            return (jax.random.normal(k, shape, jnp.float32)
                    / np.sqrt(shape[1])).astype(jnp.bfloat16)

        up = stack(keys[0], (held, d, f))
        down = stack(keys[1], (held, f, d))
        gate = stack(keys[2], (held, d, f)) if gated else None
        stacks = [s for s in (up, gate, down) if s is not None]
        read_ms = sum(s.nbytes for s in stacks) / HBM_BYTES_PER_S * 1e3
        print(f"{name}: {len(stacks)} stacks of {held} x {d} x {f}, "
              f"{sum(s.nbytes for s in stacks) / 1e6:.0f} MB, read "
              f"{read_ms:.3f} ms; stored up {up.format.layout} down "
              f"{down.format.layout}")
        # the stacks are ARGUMENTS, as a program's params are: a
        # closed-over array would be a constant of the executable

        def road(fn):
            return jax.jit(lambda r, c, w, *s: fn(
                r, c, w, s[0], s[-1], 0,
                s[1] if len(s) == 3 else None))

        dense = road(moe.dense_experts)
        for row_tile in [int(x) for x in args.row_tiles.split(",")]:
            # the row tile is the module's constant, read while a
            # call is traced: set for this tool's sweep alone
            gm.ROW_TILE = row_tile
            grouped = road(moe.grouped_experts)
            kernel = jax.jit(lambda l, h, n, *s: [
                gm.grouped_matmul(l, w, n) for w in s[:-1]]
                + [gm.grouped_matmul(h, s[-1], n)])
            for rows in ROWS:
                rk = jax.random.fold_in(keys[3], rows)
                x = jax.random.normal(rk, (rows, d), jnp.bfloat16)
                chosen, weights = moe.route_sigmoid(
                    jax.random.normal(jax.random.fold_in(rk, 1),
                                      (rows, outputs)),
                    jnp.zeros((outputs,)), top_k, 2.5)
                call = (x, chosen, weights, *stacks)
                line = (f"  tile {row_tile} rows {rows:5d} pairs here "
                        f"{int(jnp.sum(chosen < held)):5d}: ")
                a = dense(*call)
                if row_tile == gm_row_tile:
                    line += f"dense {timed(dense, call, args.calls):7.3f} "
                b = grouped(*call)
                line += f"grouped {timed(grouped, call, args.calls):7.3f}"
                # the kernel calls alone, on rows already sorted
                pairs = -(-rows * top_k // row_tile) * row_tile
                sizes = jnp.bincount(chosen.reshape(-1), length=outputs
                                     )[:held].astype(jnp.int32)
                lhs = jax.random.normal(rk, (pairs, d), jnp.bfloat16)
                hid = jax.random.normal(rk, (pairs, f), jnp.bfloat16)
                alone = timed(kernel, (lhs, hid, sizes, *stacks),
                              args.calls)
                line += (f" kernels {alone:7.3f}"
                         f" ms; max |dense - grouped| "
                         f"{float(jnp.max(jnp.abs(a - b))):.3e} of "
                         f"{float(jnp.max(jnp.abs(a))):.3e}, finite "
                         f"{bool(jnp.all(jnp.isfinite(b)))}")
                print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""One call of the grouped paged-decode kernel beside the XLA gather it
replaces, timed on the chip at the two hybrid configurations' attention
shapes (PERF.md, PR 41): 96 slots, tables of 32 pages of 64 tokens,
Solar-Open2 64 query over 8 K/V heads of 128, Nemotron 32 over 2. Not
part of the benchmark. For each shape: the kernel and the gather (ms a
call, the mean of ``--calls`` back to back) at contexts drawn like the
batch-offline cell's (a log-normal prompt plus a uniform share of a
log-normal output: about 520 tokens a slot), beside the live K/V's own
read at the chip's 819 GB/s; the kernel alone at every slot one key
(its fixed cost a slot), one whole chunk of pages (512), the head of a
second chunk (520) and a full table (2,048); the largest difference of
the two roads' results.

    chiprun -- python3 tools/paged_decode_timing.py

A chip run only: on another backend it says so and exits 2 (a CPU
timing of a TPU kernel's interpreter is no number)."""
import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from batch_shipyard_tpu.ops import paged_attention as pa  # noqa: E402

HBM_BYTES_PER_S = 819e9     # TPU v5e (benchmark/peaks.json)
SLOTS, PAGE, ENTRIES, POOL, DEPTH = 96, 64, 32, 2401, 128
# (query heads, K/V heads)
SHAPES = {"solaropen2": (64, 8), "nemotron3nano": (32, 2)}


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


def cell_lengths(rng) -> np.ndarray:
    """Contexts of 96 seated slots as traffic/batch-offline.json draws
    them: a prompt (log-normal, median 384, sigma 0.6, 64-1,024) and
    the part of an output (median 192, sigma 0.5, 64-512) decoded so
    far."""
    prompt = np.clip(np.exp(rng.normal(np.log(384), 0.6, SLOTS)),
                     64, 1024)
    output = np.clip(np.exp(rng.normal(np.log(192), 0.5, SLOTS)),
                     64, 512)
    return (prompt + rng.uniform(0, 1, SLOTS) * output).astype(np.int32)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--calls", type=int, default=50)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no chip here ({device.platform}): nothing timed")
        return 2
    print(f"device {device.device_kind} x{jax.device_count()}")
    rng = np.random.RandomState(args.seed)
    drawn = cell_lengths(rng)
    # any page for any entry: a pool as fragmented as it gets
    table = jnp.asarray(rng.randint(0, POOL - 1, (SLOTS, ENTRIES)),
                        jnp.int32)
    cases = {"cell": drawn, "1": np.full(SLOTS, 1), "512": np.full(
        SLOTS, 512), "520": np.full(SLOTS, 520), "2048": np.full(
            SLOTS, 2048)}
    for name, (heads, kv_heads) in SHAPES.items():
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        width = kv_heads * DEPTH
        q = jax.random.normal(keys[0], (SLOTS, 1, heads, DEPTH),
                              jnp.bfloat16)
        k_pages = jax.random.normal(keys[1], (POOL, PAGE, width),
                                    jnp.bfloat16)
        v_pages = jax.random.normal(keys[2], (POOL, PAGE, width),
                                    jnp.bfloat16)
        # the pool is an ARGUMENT, as a program's cache is
        gather = jax.jit(pa.paged_decode_attention_xla)
        print(f"{name}: {heads} query over {kv_heads} K/V heads of "
              f"{DEPTH}, a page {PAGE * width * 2 // 1024} KiB, mean "
              f"context {drawn.mean():.0f} (max {drawn.max()})")
        kernel = jax.jit(pa.gqa_paged_decode_attention_kernel)
        for case, lengths in cases.items():
            lengths = jnp.asarray(lengths, jnp.int32)
            operands = (q, k_pages, v_pages, table, lengths)
            pages = int(np.sum(-(-np.asarray(lengths) // PAGE)))
            read_ms = (2 * pages * PAGE * width * 2
                       / HBM_BYTES_PER_S * 1e3)
            line = (f"  lengths {case}: kernel "
                    f"{timed(kernel, operands, args.calls):.3f} ms "
                    f"(live pages' read {read_ms:.3f} ms)")
            if case == "cell":
                diff = jnp.max(jnp.abs(
                    kernel(*operands).astype(jnp.float32)
                    - gather(*operands).astype(jnp.float32)))
                line += (f", gather "
                         f"{timed(gather, operands, args.calls):.3f} "
                         f"ms, max |kernel - gather| {float(diff):.4f}")
            print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

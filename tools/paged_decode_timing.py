#!/usr/bin/env python3
"""One call of the one-program-a-slot paged-decode kernel beside the XLA
gather, timed on the chip at three configurations' attention shapes
(PERF.md, PR 41, PR 43 and PR 44), tables of 32 pages of 64 tokens:
the two hybrids at 96 slots (Solar-Open2 64 query over 8 K/V heads of
128, Nemotron 32 over 2: 8 pages a chunk) and Baichuan's MHA pool at
48 slots of which 16 are seated and 32 parked, as its batch-offline
cell holds them (32 heads of 128: 4,096 channels, 2 pages a chunk).
Not part of the benchmark. For each shape: the kernel
and the gather (ms a call, the mean of ``--calls`` back to back) at
contexts drawn like the batch-offline cell's (a log-normal prompt plus
a uniform share of a log-normal output: about 520 tokens a seated
slot), beside the live K/V's own read at the chip's 819 GB/s; the
kernel alone at every slot NO key (a skipped program's cost), one key
(its fixed cost a seated slot), 512, 520 (the head of one more chunk)
and a full table (2,048); the largest difference of the two roads'
results. Where the cell parks slots (Baichuan's): the cell's call
with the parked slots at length 1 (what a parked cursor gave the
kernel until PR 44) and at length 0 (what the step's live mask gives
it since) beside each other, and beside the seated slots' call alone
(a batch of 16): the difference over the 32 parked slots is what a
parked slot costs, in us, each way. (The back-to-back mean cannot
read under what the host takes to dispatch a call, about 0.2 ms: every
slot at NO key reads 0.21 ms and every slot at one key 0.20, both the
host's pace and not the kernel's, so PR 43's "4.7 us a slot at one
key" was that floor over 48. The cell's cases are longer than the
floor, and their differences are the device's.)

    chiprun -- python3 tools/paged_decode_timing.py [--shape baichuan7b]

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
PAGE, ENTRIES, DEPTH = 64, 32, 128
# (query heads, K/V heads, slots, of them seated in the cell, pages)
SHAPES = {"solaropen2": (64, 8, 96, 96, 2401),
          "nemotron3nano": (32, 2, 96, 96, 2401),
          "baichuan7b": (32, 32, 48, 16, 193)}


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


def cell_lengths(rng, slots: int, seated: int) -> np.ndarray:
    """Contexts of ``seated`` slots as traffic/batch-offline.json draws
    them: a prompt (log-normal, median 384, sigma 0.6, 64-1,024) and
    the part of an output (median 192, sigma 0.5, 64-512) decoded so
    far; the other slots at length 1 (a parked cursor's garbage row:
    the call as it was until the step's mask reached the kernel)."""
    prompt = np.clip(np.exp(rng.normal(np.log(384), 0.6, slots)),
                     64, 1024)
    output = np.clip(np.exp(rng.normal(np.log(192), 0.5, slots)),
                     64, 512)
    drawn = (prompt + rng.uniform(0, 1, slots) * output).astype(np.int32)
    drawn[seated:] = 1
    return drawn


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--calls", type=int, default=50)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--shape", choices=sorted(SHAPES), default=None,
                        help="time this shape alone (default: all)")
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no chip here ({device.platform}): nothing timed")
        return 2
    print(f"device {device.device_kind} x{jax.device_count()}")
    for name, (heads, kv_heads, slots, seated, pool) in SHAPES.items():
        if args.shape not in (None, name):
            continue
        rng = np.random.RandomState(args.seed)
        drawn = cell_lengths(rng, slots, seated)
        # any page for any entry: a pool as fragmented as it gets
        table = jnp.asarray(rng.randint(0, pool - 1, (slots, ENTRIES)),
                            jnp.int32)
        masked = np.where(np.arange(slots) < seated, drawn, 0)
        cases = {"cell": drawn,
                 **({"cell-masked": masked} if seated < slots else {}),
                 **{str(n): np.full(slots, n)
                    for n in (0, 1, 512, 520, 2048)}}
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        width = kv_heads * DEPTH
        q = jax.random.normal(keys[0], (slots, 1, heads, DEPTH),
                              jnp.bfloat16)
        k_pages = jax.random.normal(keys[1], (pool, PAGE, width),
                                    jnp.bfloat16)
        v_pages = jax.random.normal(keys[2], (pool, PAGE, width),
                                    jnp.bfloat16)
        # the pool is an ARGUMENT, as a program's cache is
        gather = jax.jit(pa.paged_decode_attention_xla)
        print(f"{name}: {slots} slots ({seated} seated), {heads} query "
              f"over {kv_heads} K/V heads of {DEPTH}, a page "
              f"{PAGE * width * 2 // 1024} KiB, "
              f"{pa.gqa_chunk_pages(PAGE, width, 2, ENTRIES)} pages a "
              f"chunk, mean seated context {drawn[:seated].mean():.0f} "
              f"(max {drawn.max()})")
        assert pa.paged_decode_road(
            None, grouped=kv_heads != heads) == "gqa_kernel"
        kernel = jax.jit(pa.paged_decode_attention)
        cell_ms = {}
        for case, lengths in cases.items():
            lengths = jnp.asarray(lengths, jnp.int32)
            operands = (q, k_pages, v_pages, table, lengths)
            pages = int(np.sum(-(-np.asarray(lengths) // PAGE)))
            read_ms = (2 * pages * PAGE * width * 2
                       / HBM_BYTES_PER_S * 1e3)
            cell_ms[case] = timed(kernel, operands, args.calls)
            line = (f"  lengths {case}: kernel {cell_ms[case]:.3f} ms "
                    f"(live pages' read {read_ms:.3f} ms)")
            if case.startswith("cell"):
                diff = jnp.max(jnp.abs(
                    kernel(*operands).astype(jnp.float32)
                    - gather(*operands).astype(jnp.float32)))
                line += (f", gather "
                         f"{timed(gather, operands, args.calls):.3f} "
                         f"ms, max |kernel - gather| {float(diff):.4f}")
            print(line, flush=True)
        if seated < slots:
            alone = timed(kernel, (
                q[:seated], k_pages, v_pages, table[:seated],
                jnp.asarray(drawn[:seated], jnp.int32)), args.calls)
            parked = slots - seated
            print(f"  the {seated} seated slots alone: kernel "
                  f"{alone:.3f} ms; a parked slot costs "
                  f"{(cell_ms['cell'] - alone) / parked * 1e3:.2f} us "
                  f"at length 1 and "
                  f"{(cell_ms['cell-masked'] - alone) / parked * 1e3:.2f}"
                  f" us masked to 0", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

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

``--shape kexaone`` and ``--shape sdar`` (since PR 48; not in the
default set) time the calls of those configurations' decode step as it
makes them, 96 slots all seated: K-EXAONE's two query positions a slot
over a full layer's 128-entry table and over a window layer's ring of
4 pages under a window of 128 (five of its six calls), SDAR's block
pass over a 129-entry table: the block of four positions that all see
all keys (the pass's call until PR 49), two blocks a slot that are
block-causal between them with every slot's eight positions live, and
with a slot in four closing a block and the others' second block dead
(the call since PR 49, each beside the first as a multiple of it). Their
times are the DEVICE's (the profiler's events of ``--calls`` calls,
summed: a call of 0.1 ms is far under what the host takes to dispatch
one), and the reading is what a seated slot costs a call beyond its
pages' read: every slot at ONE chunk of pages (a window layer's whole
visit), the call's device time less the live pages' read at the
chip's bandwidth, over the seated slots, in us. Until PR 48 that was
the first chunk's DMA with nothing over it, once a slot; since, the
seated slot before fetches it behind its own last chunk. ``--parent
DIR`` loads DIR/batch_shipyard_tpu/ops/paged_attention.py (a checkout
of another commit, say a ``git archive`` under .proof/) and prints
its kernel's numbers beside this tree's, one process, one chip (of the
calls its kernel can make: one that takes ``causal`` and no visible
block makes no call of two blocks).

    chiprun -- python3 tools/paged_decode_timing.py [--shape baichuan7b]
    chiprun -- python3 tools/paged_decode_timing.py --shape kexaone \\
        --parent .proof/parent

A chip run only: on another backend it says so and exits 2 (a CPU
timing of a TPU kernel's interpreter is no number)."""
import argparse
import functools
import importlib.util
import inspect
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from batch_shipyard_tpu.ops import paged_attention as pa  # noqa: E402
from benchmark import tracered  # noqa: E402

HBM_BYTES_PER_S = 819e9     # TPU v5e (benchmark/peaks.json)
PAGE, ENTRIES, DEPTH = 64, 32, 128
# (query heads, K/V heads, slots, of them seated in the cell, pages)
SHAPES = {"solaropen2": (64, 8, 96, 96, 2401),
          "nemotron3nano": (32, 2, 96, 96, 2401),
          "baichuan7b": (32, 32, 48, 16, 193)}


# the calls of a decode step that seats every slot, as the step makes
# them: (label, query heads, K/V heads, query positions a slot, table
# entries, window, visible block, of how many slots one has every
# position live (0: no live positions handed over), calls of it a step
# (0: a call beside the step's, for comparison))
STEP_CALLS = {
    "kexaone": [("full", 64, 8, 2, 128, 0, 1, 0, 1),
                ("ring", 64, 8, 2, 4, 128, 1, 0, 5)],
    "sdar": [("block", 32, 4, 4, 129, 0, 4, 0, 0),
             ("two blocks, all live", 32, 4, 8, 129, 0, 4, 0, 0),
             ("two blocks, one slot in four closing", 32, 4, 8, 129, 0,
              4, 4, 6)],
}
STEP_SLOTS = 96


def device_ms(fn, args, calls: int) -> float:
    """ms a call on the DEVICE: the profiler's operation events of
    ``calls`` calls dispatched back to back, summed (the kernel and
    what its wrapper computes before it)."""
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory(
            prefix="paged_decode_timing_") as trace_dir:
        with jax.profiler.trace(trace_dir):
            out = None
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        events = tracered.device_op_events(
            tracered.from_xplane(tracered.newest_xplane(trace_dir)))
    return sum(dur for ops in events.values()
               for _name, _start, dur in ops) / calls / 1e6


def _block_call(call, window, block, *operands):
    """``call`` of a tree whose kernel takes the visible block, the
    slots' live positions behind the five operands if there are any."""
    return call(*operands[:5], window=window, block=block,
                live_positions=operands[5] if operands[5:] else None)


def load_kernels(parent: str) -> dict:
    """{"parent": ``parent``'s module (if asked for), "change": this
    tree's}."""
    trees = {}
    if parent:
        spec = importlib.util.spec_from_file_location(
            "parent_paged_attention", os.path.join(
                parent, "batch_shipyard_tpu/ops/paged_attention.py"))
        trees["parent"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(trees["parent"])
    return {**trees, "change": pa}


def time_step_calls(name: str, args) -> None:
    """One configuration's decode-step calls, every slot seated: the
    device's time a call at one chunk a slot, at the cell's contexts
    and with two slots in three parked, each tree beside the other."""
    trees = load_kernels(args.parent)
    rng = np.random.RandomState(args.seed)
    step_ms = dict.fromkeys(trees, 0.0)
    # the cell's contexts, the same for every call of the step
    # (traffic/reason-offline.json: a prompt about 512 and the part of
    # an answer about 1,024 decoded so far), a window layer reading
    # its newest ``window`` of them
    drawn = (np.clip(np.exp(rng.normal(np.log(512), 0.7, STEP_SLOTS)),
                     128, 2048)
             + rng.uniform(0, 1, STEP_SLOTS)
             * np.clip(np.exp(rng.normal(np.log(1024), 0.5, STEP_SLOTS)),
                       256, 3072)).astype(np.int32)
    first_ms = {}
    for label, heads, kv_heads, positions, entries, window, block, \
            closing, per_step in STEP_CALLS[name]:
        width = kv_heads * DEPTH
        chunk = pa.gqa_chunk_pages(PAGE, width, 2, entries)
        pool = STEP_SLOTS * min(entries, 48) + 1
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        q = jax.random.normal(
            keys[0], (STEP_SLOTS, positions, heads, DEPTH), jnp.bfloat16)
        k_pages = jax.random.normal(keys[1], (pool, PAGE, width),
                                    jnp.bfloat16)
        v_pages = jax.random.normal(keys[2], (pool, PAGE, width),
                                    jnp.bfloat16)
        table = jnp.asarray(
            rng.randint(0, pool - 1, (STEP_SLOTS, entries)), jnp.int32)
        one_chunk = np.full(
            STEP_SLOTS, window + positions if window else chunk * PAGE)
        cases = {"one chunk a slot": one_chunk, "cell": drawn,
                 "cell, two in three parked": np.where(
                     np.arange(STEP_SLOTS) % 3 == 0, drawn, 0)}
        print(f"{name} {label}: {STEP_SLOTS} slots, {positions} query "
              f"positions of {heads} heads over {kv_heads} K/V heads "
              f"of {DEPTH}, a table of {entries}, window {window}, "
              f"{chunk} pages a chunk, {per_step} such calls a step")
        live = () if not closing else (jnp.where(
            jnp.arange(STEP_SLOTS) % closing == 0, positions, block),)
        kernels = {}
        for tree, module in trees.items():
            call = module.gqa_paged_decode_attention_kernel
            if "block" in inspect.signature(call).parameters:
                kernels[tree] = jax.jit(functools.partial(
                    _block_call, call, window, block))
            elif block in (1, positions) and not live:
                kernels[tree] = jax.jit(functools.partial(
                    call, window=window, causal=block == 1))
        for case, lengths in cases.items():
            low = np.maximum(lengths - (positions - 1 + window), 0) \
                if window else np.zeros_like(lengths)
            pages = int(np.sum(np.where(
                lengths > 0, (lengths - 1) // PAGE - low // PAGE + 1, 0)))
            read_ms = (2 * pages * PAGE * width * 2
                       / HBM_BYTES_PER_S * 1e3)
            seated = int(np.sum(lengths > 0))
            operands = (q, k_pages, v_pages, table,
                        jnp.asarray(lengths, jnp.int32)) + live
            outs = {}
            for tree, kernel in kernels.items():
                outs[tree] = np.asarray(kernel(*operands), np.float32)
                ms = device_ms(kernel, operands, args.calls)
                if case == "cell":
                    step_ms[tree] += per_step * ms
                beside = first_ms.setdefault((case, tree), ms)
                print(f"  {case}, {tree}: device {ms:.4f} ms a call "
                      f"(live pages' read {read_ms:.4f} ms: "
                      f"{100 * read_ms / ms:.1f} % of it), "
                      f"{(ms - read_ms) / seated * 1e3:.2f} us a seated "
                      f"slot beyond the read, {ms / beside:.3f} times "
                      f"the first call listed", flush=True)
            if len(outs) == 2:
                same = np.array_equal(outs["parent"], outs["change"])
                print(f"  {case}: the two trees' results are "
                      f"{'bit for bit the same' if same else 'DIFFERENT'}")
    print(f"{name}: the step's paged decode calls at the cell's "
          f"contexts, " + ", ".join(
              f"{tree} {ms:.3f} ms" for tree, ms in step_ms.items()))


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
    parser.add_argument("--shape",
                        choices=sorted(SHAPES) + sorted(STEP_CALLS),
                        default=None,
                        help="time this shape alone (default: the "
                        "one-token shapes)")
    parser.add_argument("--parent", default="",
                        help="a checkout of another commit whose "
                        "kernel is timed beside this tree's "
                        "(--shape kexaone / sdar)")
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"no chip here ({device.platform}): nothing timed")
        return 2
    print(f"device {device.device_kind} x{jax.device_count()}")
    if args.shape in STEP_CALLS:
        time_step_calls(args.shape, args)
        return 0
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

#!/usr/bin/env python3
"""Probe, not a benchmark: what do INACTIVE slots cost the decode step?

A freed slot keeps 'decoding' (masked) through the scratch page and
its per-layer ``length`` keeps growing by one a step, so the paged
kernel computes ceil(length / page) blocks of the scratch page for it.
This builds the benchmark configuration's engine (the benchmark's own
weights and engine shim), seats 15 requests, and times engine.step()
while the other slots' lengths are ~0, half of max_decode_len, all of
it, and ~0 again. One JSON line a case. PERF.md (section 5, PR 25) has
the readings on a v5e: 21.5 / 28.9 / 35.9 / 22.1 ms.

    chiprun --chips 1 -- python3 tools/inactive_slot_probe.py
    JAX_PLATFORMS=cpu python tools/inactive_slot_probe.py --tiny
                                    (control flow only, no timing)"""
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
from benchmark import flops, harness, spec, weights  # noqa: E402

harness.place_compile_cache(ROOT)        # before anything imports jax

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from batch_shipyard_tpu.models.serving import Request  # noqa: E402
from benchmark.drivers import serve as drv  # noqa: E402

TINY = "--tiny" in sys.argv
bench = spec.load_benchmark(ROOT)
cell = spec.load_cell("baichuan7b.batch-offline", ROOT, bench)
model = harness.merged(cell.config, TINY)
dims = flops.model_dims(model)
params = weights.make_params(dims, 12345, jnp.bfloat16)
engine = drv.build_engine(None, model, params)
ACTIVE = 1 if TINY else 15
PROMPT, NEW = (20, 150) if TINY else (500, 260)
BIG = engine.max_decode_len
rng = np.random.RandomState(0)
for i in range(ACTIVE):
    engine.submit(Request(
        f"r{i}", [int(t) for t in rng.randint(1, dims["vocab"], (PROMPT,))],
        max_new_tokens=NEW))
for _ in range(ACTIVE + 25):   # admit all (one prefill a slot), warm
    engine.step()
assert sum(s.request is not None for s in engine._slots) == ACTIVE


def timed(label, n=10 if TINY else 50):
    jax.block_until_ready(engine.cache)
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        engine.step()
        times.append((time.perf_counter() - t0) * 1e3)
    lengths = np.asarray(jax.tree_util.tree_leaves(
        engine.cache["layer_0"]["attn"]["length"])[0])
    print(json.dumps({"case": label, "step_p50_ms": float(np.median(times)),
                      "step_min_ms": float(min(times)),
                      "inactive_length_now": int(lengths[-1]),
                      "active_length_now": int(lengths[0])}), flush=True)


def set_inactive(value):
    inactive = np.asarray([s.request is None for s in engine._slots])

    def fix(path, leaf):
        if path[-1].key == "length":
            return jnp.where(jnp.asarray(inactive), jnp.int32(value), leaf)
        return leaf
    engine.cache = jax.tree_util.tree_map_with_path(fix, engine.cache)


set_inactive(0)
timed("inactive lengths 0..60")
set_inactive(BIG // 2)
timed(f"inactive lengths {BIG // 2}..")
set_inactive(BIG)
timed(f"inactive lengths {BIG}..")
set_inactive(0)
timed("inactive lengths 0..60 again")

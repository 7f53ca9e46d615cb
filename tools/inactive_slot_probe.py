#!/usr/bin/env python3
"""Probe, not a benchmark: what do IDLE slots cost the decode step?

A slot without a request stays in the full-batch decode step. Until
PR 28 a freed slot's cursor kept its last request's length and grew
by one a step, and the paged kernel computed ceil(cursor / page)
blocks of the scratch page for it (on a v5e, 15 live slots and 33
idle: a step of 21.5 ms with the idle cursors near 0, 28.9 at 1,024,
35.9 at 2,048; PERF.md section 6, PR 25). Since PR 28 every step
program parks an idle slot's cursor at 0, which left it one block of
the scratch page a layer (length 1: two page DMAs and a whole chunk
through the MXU, 3.0 us a slot a layer at Baichuan's 4,096 channels;
PERF.md section 6, PR 44). Since PR 44 the step's ``active`` mask
reaches the kernel as length 0 whatever the cursor reads: an idle
slot's program fetches no page and computes no tile and costs 0.28 us
(a grid step, the query block in, a zero block out; same section),
and the kernel's work a layer is occupancy()'s kv_blocks_attended,
the seated slots' pages alone.
This is the after-picture: the benchmark configuration's engine (the
benchmark's own weights and engine shim), 15 requests seated, and for
each of 0, half of and all of max_decode_len the idle slots' cursors
are SET to that, one step is run and timed (its kernel is handed
length 0 for them all the same; its row scatter still writes at what
was set), the idle cursors are read back (0), and the steps after it
are timed. One JSON line a case; the last line says whether every
case read 0 one step later and how far the cases' step times lie
apart.

    chiprun --chips 1 -- python3 tools/inactive_slot_probe.py
    JAX_PLATFORMS=cpu python tools/inactive_slot_probe.py --tiny
                                    (control flow only, no timing)"""
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
CELL = "baichuan7b.batch-offline"


def main(argv=None) -> int:
    tiny = "--tiny" in (sys.argv[1:] if argv is None else argv)
    sys.path.insert(0, str(ROOT))
    from benchmark import harness, spec, weights
    harness.place_compile_cache(ROOT)    # before anything imports jax
    import jax
    import jax.numpy as jnp
    import numpy as np

    from batch_shipyard_tpu.models.serving import Request
    from benchmark.drivers import serve as drv

    cell = spec.load_cell(CELL, ROOT, spec.load_benchmark(ROOT))
    model = harness.merged(cell.config, tiny)
    module = spec.load_model(model, ROOT)
    dims = module.dims(model)
    params = weights.make_params(module.param_leaves(dims), 12345,
                                 jnp.bfloat16)
    engine = drv.build_engine(module, model, params)
    live, prompt, new, timed = (1, 20, 150, 5) if tiny else (
        15, 500, 260, 30)
    rng = np.random.RandomState(0)
    for i in range(live):
        engine.submit(Request(
            f"r{i}", [int(t) for t in rng.randint(1, dims["vocab"],
                                                  (prompt,))],
            max_new_tokens=new))
    for _ in range(live + 25):   # admit all (one prefill a slot), warm
        engine.step()
    idle = np.asarray([s.request is None for s in engine._slots])
    assert int((~idle).sum()) == live

    def cursors():
        return np.asarray(engine.cache["layer_0"]["attn"]["length"])

    def set_idle(value):
        def fix(path, leaf):
            if path[-1].key == "length":
                return jnp.where(jnp.asarray(idle), jnp.int32(value),
                                 leaf)
            return leaf
        engine.cache = jax.tree_util.tree_map_with_path(fix,
                                                        engine.cache)

    def step_ms():
        """Until the step this call DISPATCHED is done on the device:
        the engine itself only waits for the step before it."""
        t0 = time.perf_counter()
        engine.step()
        jax.block_until_ready(engine.cache)
        return (time.perf_counter() - t0) * 1e3

    big = engine.max_decode_len
    rows = []
    for value in (0, big // 2, big, 0):
        set_idle(value)
        jax.block_until_ready(engine.cache)
        # What the first step's kernel computes in a layer, by the
        # device's cursors (the pending row written) of the slots the
        # step's mask leaves live: whatever was set behind the host's
        # back, the idle slots are handed over at length 0, so this IS
        # the host's count.
        first_blocks = int(
            (-(-(cursors() + 1) // engine.page_size))[~idle].sum())
        blocks = engine.occupancy()["kv_blocks_attended"]
        first = step_ms()
        after = cursors()
        times = [step_ms() for _ in range(timed)]
        rows.append({
            "idle_cursors_set_to": value,
            "first_step_ms": first,
            "first_step_kv_blocks": first_blocks,
            "idle_cursor_max_one_step_later": int(after[idle].max()),
            "step_p50_ms": float(np.median(times)),
            "step_min_ms": float(min(times)),
            "live_cursor_now": int(cursors()[~idle].max()),
            "kv_blocks_attended": blocks})
        print(json.dumps(rows[-1]), flush=True)
    assert sum(s.request is not None for s in engine._slots) == live
    p50 = [row["step_p50_ms"] for row in rows]
    parked = all(row["idle_cursor_max_one_step_later"] == 0
                 for row in rows)
    print(json.dumps({
        "live_slots": live, "idle_slots": int(idle.sum()),
        "idle_cursors_parked": parked,
        "step_p50_spread_pct": (max(p50) - min(p50)) / min(p50) * 100,
        "device": jax.devices()[0].device_kind}), flush=True)
    return 0 if parked else 1


if __name__ == "__main__":
    sys.exit(main())

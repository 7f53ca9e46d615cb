#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in one process that owns the
cell's chips:

    python benchmark/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

It makes its inputs and weights from --seed, warms up every shape the
cell's traffic uses (set-up), measures for --seconds, checks what the
window produced against the plain reference, prints each number
compared beside its limit (as the last lines of its standard error,
and under ``check``, the last key of the result), and prints as its
LAST line one JSON object with the keys correct, attempted, failed,
metrics, device (and breakdown with --trace 1), then check. With
--trace 0 the metrics are the cell's end-to-end metrics; with
--trace 1 its per-layer metrics.

Off a TPU, with fewer chips than the cell asks for, on a device_kind
that benchmark/peaks.json does not hold, or without the rest of the
repo beside it, it exits non-zero and prints no result: it never falls
back to the CPU. ``--rehearse-tiny`` is the sandbox's control-flow
rehearsal (tiny sizes, any backend); it prints no metric at all."""

from __future__ import annotations

import time

_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

EXIT_NO_CHIP = 2
EXIT_NO_PROGRAM = 3


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--rehearse-tiny", action="store_true",
        help="control-flow rehearsal at tiny sizes on any backend; "
             "prints no metric. Not a measurement.")
    args = parser.parse_args(argv)

    from benchmark import harness, layers, peaks, spec
    bench = spec.load_benchmark(ROOT)
    cell = spec.load_cell(args.workload, ROOT, bench)
    harness.place_compile_cache(ROOT)
    try:
        import batch_shipyard_tpu  # noqa: F401 - the system under test
    except ImportError as exc:
        print(f"benchmark: the program is not beside the benchmark "
              f"({exc})", file=sys.stderr)
        return EXIT_NO_PROGRAM
    ctx = harness.RunContext(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), tiny=args.rehearse_tiny, root=ROOT,
        started=_PROCESS_START, out_dir=harness.fresh_out_dir(ROOT))
    try:
        harness.find_devices(ctx)
    except (harness.NoChip, peaks.UnknownDevice) as exc:
        print(f"benchmark: {exc}", file=sys.stderr)
        return EXIT_NO_CHIP
    ctx.note(f"cell {cell.name}: config {cell.config_name}, traffic "
             f"{cell.traffic_name} ({cell.kind}), seed {args.seed}, "
             f"{args.seconds:g}s, trace {args.trace}"
             + (", REHEARSAL (no metric is a measurement)"
                if ctx.tiny else ""))

    # A cell's kind picks its driver: drivers/<kind>.py with ``-`` read
    # as ``_``. A new kind is a new module.
    driver = spec.load_module(
        ROOT, bench, f"drivers/{cell.kind.replace('-', '_')}.py")
    outcome = driver.run(ctx)

    device = harness.device_report(ctx, outcome["memory_peak_bytes"])
    line = {"correct": bool(outcome["correct"]),
            "attempted": int(outcome["attempted"]),
            "failed": int(outcome["failed"]),
            "metrics": {}, "device": device}
    if ctx.trace:
        profile = outcome.get("profile")
        if profile is not None and not ctx.tiny:
            device["busy_s"] = profile["busy_s"]
            device["window_s"] = profile["window_s"]
            line["breakdown"] = profile["breakdown"]
        metrics = layers.read_all(cell, outcome["obs"], ROOT, bench)
        for kernel, bound in sorted(
                outcome["obs"].get("roofline_bound", {}).items()):
            ctx.note(f"roofline: {kernel} is {bound}-bound")
    else:
        metrics = {m["name"]: {"value": float(
            outcome["values"][m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end}
    if ctx.tiny:
        ctx.note(f"rehearsal values (NOT measurements, not reported): "
                 f"{json.dumps(metrics)}")
    else:
        line["metrics"] = metrics
    # each number compared beside its limit: the last lines of the
    # standard error, and the last key of the result (a number that is
    # not finite reads null there, so that the line stays JSON)
    line["check"] = {
        name: {"value": pair["value"] if pair["value"] is not None
               and math.isfinite(pair["value"]) else None,
               "limit": pair["limit"]}
        for name, pair in outcome["compared"].items()}
    for name, pair in outcome["compared"].items():
        print(f"check {name}: {pair['value']!r} (limit <= "
              f"{pair['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

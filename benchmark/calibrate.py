#!/usr/bin/env python3
"""Calibration of a serving cell on the chip, set-up paid once:

    python benchmark/calibrate.py --workload <cell> --seconds <s> \
        [--rates r1,r2,...]       the rate sweep that finds the knee
        [--seeds a,b,c,...]       the check's numbers, seed by seed
        [--control [N]]           the configuration's control (its
                                  ``check.control``, the N-th where it
                                  lists several; 0 if N is left out):
                                  overrides for the model module's
                                  program_model, and under "decisions"
                                  what is done to the engine's record
        [--kv-int8]               the control {"kv_cache_dtype":
                                  "int8"}: the program's own int8 KV
                                  cache switched on
        [--dump DIR]              every served token's readings, one
                                  JSON file a window

Each window prints one JSON line. It is not the benchmark: it reports
no metric of record, and the driver never runs it. The limits in the
configuration files and the rate in traffic/chat-online.json were set
from its readings (PERF.md has them)."""

from __future__ import annotations

import time

_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def _halves(rows: list) -> dict:
    """First-token time in the window's first and second half: a
    backlog that grows shows as a second half far above the first."""
    from benchmark import stats
    window = sorted((r for r in rows if r["in_window"] and r["ok"]
                     and r.get("due") is not None),
                    key=lambda r: r["due"])
    if len(window) < 4:
        return {}
    ttft = [(r["token_times"][0] - r["due"]) * 1e3 for r in window]
    half = len(ttft) // 2
    return {"ttft_p50_first_half_ms": stats.percentile(ttft[:half], 50),
            "ttft_p50_second_half_ms": stats.percentile(ttft[half:], 50),
            "last_end_after_window_s": max(
                r["ended"] for r in window) - max(
                    r["due"] for r in window)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--rates", default="")
    parser.add_argument("--seeds", default="1")
    parser.add_argument("--control", type=int, nargs="?", const=0,
                        default=None)
    parser.add_argument("--kv-int8", action="store_true")
    parser.add_argument("--dump", default="")
    parser.add_argument("--rehearse-tiny", action="store_true")
    args = parser.parse_args(argv)

    from benchmark import check, harness, peaks, spec
    cell = spec.load_cell(args.workload, ROOT)
    harness.place_compile_cache(ROOT)     # before anything imports jax
    from benchmark.drivers import serve
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctx = harness.RunContext(
        cell=cell, seed=seeds[0], seconds=args.seconds, trace=False,
        tiny=args.rehearse_tiny, root=ROOT, started=_PROCESS_START,
        out_dir=harness.fresh_out_dir(ROOT))
    try:
        harness.find_devices(ctx)
    except (harness.NoChip, peaks.UnknownDevice) as exc:
        print(f"calibrate: {exc}", file=sys.stderr)
        return 2
    control = {"kv_cache_dtype": "int8"} if args.kv_int8 else None
    if args.control is not None:
        stated = cell.config["check"].get("control")
        if stated is None:
            parser.error(f"{cell.config_name} states no check.control")
        control = check.controls(stated)[args.control]
    session = serve.Session(ctx, control=control)
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    first = True
    for seed in seeds:
        for rate in rates:
            if not first:
                session.reseed(seed)
            first = False
            if rate is not None:
                session.traffic["arrivals"]["rate_per_s"] = rate
            measured = session.window()
            line = {"seed": seed, "rate_per_s": rate,
                    "control": control,
                    **{k: v for k, v in measured["values"].items()
                       if k != "setup_s"},
                    **_halves(measured["rows"])}
            if rate is None:
                checked = session.check(measured["rows"])
                line["numbers"] = checked["numbers"]
                line["checked_requests"] = checked["requests"]
                line["checked_tokens"] = checked["tokens"]
                line["check_seconds"] = checked["seconds"]
                if args.dump:
                    os.makedirs(args.dump, exist_ok=True)
                    name = (f"{args.workload}."
                            f"{'control' if control else 'sound'}"
                            f".{seed}.json")
                    with open(os.path.join(args.dump, name), "w",
                              encoding="utf-8") as fh:
                        json.dump(checked["readings"], fh)
            print("CALIBRATE " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

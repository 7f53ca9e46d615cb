"""A number from the launches the engine landed: the ``landed`` list
of its ``serve_step`` rows (one entry a decode step or prefill whose
result the call read back: ``kind``, ``period_ms`` = how long it held
the head of the device's queue as the host saw it, ``ready`` = the
device had finished before the host came to ask, and ``rows`` or
``path`` / ``bucket`` / ``tokens``), and the rows' ``no_work_seconds``
(the dry spell, if any, that the call's first dispatch ended):
params {"kind": "decode" | "prefill" | "all",
        "value": "share_of_window" | "ms_per_ktoken" | "padding_pct"
                 | "period_ms" | "ready_pct" | "no_work_pct",
        "pct": percentile (``period_ms`` only)}.

    share_of_window  the launches' ``period_ms`` summed, % of the window
    ms_per_ktoken    prefills: ``period_ms`` summed over ``bucket``
                     summed, x 1000: a padded prompt token's price
    padding_pct      prefills: 100 x (1 - ``tokens`` / ``bucket``), the
                     sums': what a prefill computes for padding
    period_ms        the ``pct``-th percentile of the ``period_ms``
    ready_pct        landings that found their result ready, % of all
    no_work_pct      the rows' ``no_work_seconds`` summed, % of the
                     window: the device dry because nothing was there
                     to run

The rows are those of the WHOLE window (step_rows.window_rows: by
``mono_start``, from the run's own output directory), not of the
profiler's slice, and the recorder that writes them runs outside the
profiler. No directory, no rows, or rows of a program that writes no
such list (the parent of the PR that brought it) reads None."""

import pathlib
import re

from benchmark import spec, stats, tracered

PROGRAM_LINE = "XLA Modules"


def launches(rows: list, kind: str = "all") -> list:
    return [launch for row in rows for launch in row.get("landed", ())
            if kind in ("all", launch["kind"])]


def value(rows: list, window_s: float, params: dict):
    if not rows or not window_s:
        return None
    if params["value"] == "no_work_pct":
        dry = [row["no_work_seconds"] for row in rows
               if "no_work_seconds" in row]
        return 100.0 * sum(dry) / window_s if dry else None
    picked = launches(rows, params["kind"])
    if not picked:
        return None
    periods = [launch["period_ms"] for launch in picked]
    if params["value"] == "share_of_window":
        return 100.0 * sum(periods) / 1e3 / window_s
    if params["value"] == "period_ms":
        return stats.percentile(periods, float(params["pct"]))
    if params["value"] == "ready_pct":
        return 100.0 * sum(bool(launch["ready"]) for launch in picked) \
            / len(picked)
    padded = sum(launch["bucket"] for launch in picked)
    if not padded:
        return None
    if params["value"] == "ms_per_ktoken":
        return 1e3 * sum(periods) / padded
    return 100.0 * (1.0 - sum(launch["tokens"] for launch in picked)
                    / padded)


def describe(rows: list, window_s: float) -> str:
    """One line for PERF.md: the window's launches by kind and, the
    prefills, by bucket."""
    decode, prefill = launches(rows, "decode"), launches(rows, "prefill")
    every = decode + prefill
    by_bucket: dict = {}
    for launch in prefill:
        by_bucket.setdefault(launch["bucket"], []).append(
            launch["period_ms"])
    padded = sum(launch["bucket"] for launch in prefill)
    dry = sum(row.get("no_work_seconds", 0.0) for row in rows)
    return (
        f"launches landed in the window: decode {len(decode)} (p50 "
        f"{stats.percentile([x['period_ms'] for x in decode], 50)} ms, "
        f"{sum(x['period_ms'] for x in decode) / 1e3:.3f} s), prefill "
        f"{len(prefill)} "
        f"({sum(x['period_ms'] for x in prefill) / 1e3:.3f} s; a "
        f"bucket: " + ", ".join(
            f"{bucket} x{len(ms)} mean {sum(ms) / len(ms):.2f} ms"
            for bucket, ms in sorted(by_bucket.items()))
        + f"; tokens {sum(x['tokens'] for x in prefill)} of {padded} "
        f"padded) of {window_s:.1f} s; found ready "
        f"{sum(bool(x['ready']) for x in every)} of {len(every)}; "
        f"mean behind "
        f"{sum(x['behind_ms'] for x in every) / max(1, len(every)):.2f}"
        f" ms; no_work {dry:.3f} s; under neither a launch nor "
        f"no_work (the device's queue empty with work there) "
        f"{window_s - dry - sum(x['period_ms'] for x in every) / 1e3:.3f}"
        f" s")


def against_the_trace(rows: list, profile: dict) -> str:
    """One line for PERF.md: the launches that lie inside the
    profiler's slice, by kind, against the device trace's own program
    launches (the "XLA Modules" line: one event a launch, from its
    first operation to its last). Where the host waited for the
    device a ``period_ms`` is the program's device time and the gap
    to the next; where it found the result ready it holds the host's
    lateness."""
    inside = [launch for launch in launches(rows)
              if profile["started"] <= launch["landed_at"]
              - launch["period_ms"] / 1e3
              and launch["landed_at"] <= profile["stopped"]]
    ours = []
    for kind in ("decode", "prefill"):
        picked = [x for x in inside if x["kind"] == kind]
        if picked:
            ms = sum(x["period_ms"] for x in picked)
            ours.append(
                f"{kind} {len(picked)} launches, {ms / 1e3:.4f} s, "
                f"mean {ms / len(picked):.3f} ms, ready "
                f"{sum(bool(x['ready']) for x in picked)}")
    programs: dict = {}
    for plane in profile["trace"]["planes"]:
        if not tracered.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            if line["name"] != PROGRAM_LINE:
                continue
            for name, _start, dur in line["events"]:
                programs.setdefault(re.sub(r"\(.*", "", name),
                                    []).append(dur / 1e6)
        if programs:
            break       # the first device that ran any
    theirs = [f"{name} {len(ms)} launches, {sum(ms) / 1e3:.4f} s, "
              f"mean {sum(ms) / len(ms):.3f} ms"
              for name, ms in sorted(programs.items(),
                                     key=lambda kv: -sum(kv[1]))]
    return (f"inside the traced slice of "
            f"{profile['stopped'] - profile['started']:.3f} s, the "
            f"engine's landings: " + ("; ".join(ours) or "none")
            + f" | the device trace's {PROGRAM_LINE}: "
            + ("; ".join(theirs) or "none"))


def read(obs, params):
    if "step_rows" not in obs:
        out_dir = obs.get("out_dir")
        window_rows = spec.load_module(
            spec.ROOT, spec.load_benchmark(),
            "layer_metrics/readers/step_rows.py").window_rows
        obs["step_rows"] = window_rows(pathlib.Path(out_dir)) \
            if out_dir else ([], 0.0)
    rows, window_s = obs["step_rows"]
    if "launch_rows_described" not in obs:
        obs["launch_rows_described"] = True
        if launches(rows):
            print(describe(rows, window_s), flush=True)
            profile = obs.get("profile")
            if profile and profile.get("trace") and \
                    profile.get("started") is not None:
                print(against_the_trace(rows, profile), flush=True)
    return value(rows, window_s, params)

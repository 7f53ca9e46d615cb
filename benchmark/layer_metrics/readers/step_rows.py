"""A number from the engine's own ``serve_step`` rows (one per engine
step, written by ContinuousBatcher while the span recorder is on):
params {"steps": "with_prefill" | "without_prefill" | "all",
        "phases": [phase, ...],
        "value": "wall_less" | "per_prefill" | "share_of_window",
        "pct": percentile of the per-step values (not for a share)}.

    wall_less        a step's wall time less its ``phases``
    per_prefill      a step's ``phases`` over the requests it admitted
    share_of_window  the rows' ``phases`` summed, as % of the window

Only rows whose step began inside the measured window count: the
rows' ``mono_start`` and the load generator's ``window_start`` are
both CLOCK_MONOTONIC. The rows and the window are what the traced run
leaves in its own output directory (spans.jsonl, loadgen.json), which
the driver names in ``obs["out_dir"]``; no directory named, nothing
there, or a program that writes no such rows, reads None."""

import json
import pathlib

from benchmark import stats

ROW_KIND = "serve_step"
STEPS = {"with_prefill": lambda attrs: attrs["prefills"] > 0,
         "without_prefill": lambda attrs: attrs["prefills"] == 0,
         "all": lambda attrs: True}


def window_rows(out_dir) -> tuple[list, float]:
    """(rows of the window, each its attrs with ``wall_ms`` added;
    the window's seconds), or ([], 0.0) where a file is missing."""
    try:
        with open(out_dir / "loadgen.json", encoding="utf-8") as fh:
            loaded = json.load(fh)
        with open(out_dir / "spans.jsonl", encoding="utf-8") as fh:
            spans = [json.loads(line) for line in fh if line.strip()]
    except OSError:
        return [], 0.0
    start = loaded["window_start"]
    end = start + loaded["window_s"]
    rows = [dict(span["attrs"],
                 wall_ms=(span["end"] - span["start"]) * 1e3)
            for span in spans if span["kind"] == ROW_KIND
            and start <= span["attrs"]["mono_start"] < end]
    return rows, float(loaded["window_s"])


def value(rows: list, window_s: float, params: dict):
    picked = [row for row in rows if STEPS[params["steps"]](row)]
    if not picked or not window_s:
        return None
    phase_ms = [sum(row[f"{name}_ms"] for name in params["phases"])
                for row in picked]
    if params["value"] == "share_of_window":
        return 100.0 * sum(phase_ms) / 1e3 / window_s
    if params["value"] == "per_prefill":
        series = [ms / row["prefills"]
                  for ms, row in zip(phase_ms, picked)]
    else:
        series = [row["wall_ms"] - ms
                  for ms, row in zip(phase_ms, picked)]
    return stats.percentile(series, float(params["pct"]))


def describe(rows: list, phases: tuple) -> str:
    """One line for PERF.md: how the rows compare with what the
    benchmark measures from outside."""
    wall = sum(row["wall_ms"] for row in rows)
    covered = sum(row[f"{name}_ms"] for row in rows for name in phases)
    decode = [row["wall_ms"] for row in rows if not row["prefills"]]
    return (f"serve_step rows in the window: {len(rows)} "
            f"({len(decode)} without a prefill), wall p50 "
            f"{stats.percentile([r['wall_ms'] for r in rows], 50):.3f}"
            f" ms, mean slots_active "
            f"{sum(r['slots_active'] for r in rows) / len(rows):.3f} "
            f"of {rows[0]['slots_total']}, phases cover "
            f"{100.0 * covered / wall:.2f} % of {wall / 1e3:.3f} s; "
            f"mean ms a step: " + ", ".join(
                f"{name} "
                f"{sum(r[f'{name}_ms'] for r in rows) / len(rows):.3f}"
                for name in phases))


def read(obs, params):
    if "step_rows" not in obs:
        out_dir = obs.get("out_dir")
        obs["step_rows"] = window_rows(pathlib.Path(out_dir)) \
            if out_dir else ([], 0.0)
        rows = obs["step_rows"][0]
        if rows:
            phases = tuple(key[:-3] for key in rows[0]
                           if key.endswith("_ms") and key not in
                           ("wall_ms", "compile_ms"))
            print(describe(rows, phases), flush=True)
    return value(*obs["step_rows"], params)

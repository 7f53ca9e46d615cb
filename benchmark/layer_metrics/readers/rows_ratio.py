"""One attr of the engine's ``serve_step`` rows as a percentage of
another (the rows layer_metrics/readers/step_rows.py reads):
params {"part": attr, "whole": attr, "take": "sum" | "max"}.

    sum  the rows' ``part`` summed over their ``whole`` summed (a
         share of work over the window: the keys the window layers
         attended of the keys the full layers attended)
    max  the largest ``part`` / ``whole`` of any row (a peak: pages in
         use of pages in all)

Only rows whose step began inside the measured window count, and of
those the ones that carry both attrs with ``whole`` above 0. No such
row (a program that does not write the attrs) reads None."""

import pathlib

from benchmark import spec


def value(rows: list, params: dict):
    pairs = [(row[params["part"]], row[params["whole"]])
             for row in rows
             if params["part"] in row and row.get(params["whole"])]
    if not pairs:
        return None
    if params["take"] == "max":
        return 100.0 * max(part / whole for part, whole in pairs)
    return 100.0 * sum(part for part, _ in pairs) \
        / sum(whole for _, whole in pairs)


def read(obs, params):
    if "step_rows" not in obs:
        out_dir = obs.get("out_dir")
        rows = spec.load_module(
            spec.ROOT, spec.load_benchmark(),
            "layer_metrics/readers/step_rows.py").window_rows
        obs["step_rows"] = rows(pathlib.Path(out_dir)) if out_dir \
            else ([], 0.0)
    return value(obs["step_rows"][0], params)

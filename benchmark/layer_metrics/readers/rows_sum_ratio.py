"""Sums of attrs of the engine's ``serve_step`` rows over sums of
others (the rows layer_metrics/readers/step_rows.py reads): params
{"part": [attr, ...], "whole": [attr, ...], "scale": number}.

rows_ratio takes one attr over one attr as a percentage. A
block-diffusion engine counts its denoise passes and its commit passes
apart (``block_denoise_passes``, ``block_commit_passes``), and what
its schedule is worth is tokens landed over BOTH: this reader gives
``scale`` x (the rows' ``part`` attrs summed) / (their ``whole`` attrs
summed), 1 for a quotient, 100 for a share.

Only rows whose step began inside the measured window count, and of
those the ones that carry every attr named. No such row (a program
that does not write the attrs), or a ``whole`` of 0, reads None."""

import pathlib

from benchmark import spec


def value(rows: list, params: dict):
    names = list(params["part"]) + list(params["whole"])
    rows = [row for row in rows if all(name in row for name in names)]
    whole = sum(row[name] for row in rows for name in params["whole"])
    if not rows or not whole:
        return None
    return float(params.get("scale", 1)) * sum(
        row[name] for row in rows for name in params["part"]) / whole


def read(obs, params):
    if "step_rows" not in obs:
        out_dir = obs.get("out_dir")
        rows = spec.load_module(
            spec.ROOT, spec.load_benchmark(),
            "layer_metrics/readers/step_rows.py").window_rows
        obs["step_rows"] = rows(pathlib.Path(out_dir)) if out_dir \
            else ([], 0.0)
    return value(obs["step_rows"][0], params)

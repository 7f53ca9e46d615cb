"""Per-layer metrics: each is a data file of its own,
``layer_metrics/<name>.json``, naming a small reader
(``layer_metrics/readers/<reader>.py``, ``read(obs, params)``) and its
parameters. A reader that finds nothing to read returns None and the
metric is left out of the line. Adding a metric adds files; nothing
here is edited."""

from __future__ import annotations

import math
import pathlib
from typing import Optional

from benchmark import spec


def read_all(cell: spec.Cell, obs: dict,
             root: pathlib.Path = spec.ROOT,
             bench: Optional[dict] = None) -> dict:
    """{metric name: {"value", "unit"}} for the cell's per-layer
    metrics that found something to read."""
    bench = bench or spec.load_benchmark(root)
    out = {}
    for metric in cell.per_layer:
        definition = spec.layer_metric_file(metric["name"], root, bench)
        reader = spec.load_module(
            root, bench,
            f"layer_metrics/readers/{definition['reader']}.py")
        value = reader.read(obs, definition.get("params", {}))
        if value is None or not math.isfinite(value):
            continue
        out[metric["name"]] = {"value": float(value),
                               "unit": metric["unit"]}
    return out

"""A routed model's expert counters from the engine's ``serve_step``
rows (the rows layer_metrics/readers/step_rows.py reads; here the
attrs ``expert_pairs_here``, ``expert_pairs_chosen`` and
``experts_held``, which a program without routed layers does not
write): params {"value": "rows_per_expert" | "routed_here_pct"}.

    rows_per_expert  mean, over the rows that landed a decode step, of
                     the (row, choice) pairs computed here over the
                     expert matrices held here (all routed layers):
                     the rows each held expert multiplies in a step
    routed_here_pct  the pairs computed here as % of all pairs chosen

Only rows whose step began inside the measured window count. No rows,
or rows without these attrs, reads None."""

import pathlib

from benchmark import spec


def value(rows: list, params: dict):
    landed = [row for row in rows
              if row.get("expert_pairs_chosen") and row.get("experts_held")]
    if not landed:
        return None
    if params["value"] == "routed_here_pct":
        return 100.0 * sum(row["expert_pairs_here"] for row in landed) \
            / sum(row["expert_pairs_chosen"] for row in landed)
    return sum(row["expert_pairs_here"] / row["experts_held"]
               for row in landed) / len(landed)


def read(obs, params):
    if "step_rows" not in obs:
        out_dir = obs.get("out_dir")
        rows = spec.load_module(
            spec.ROOT, spec.load_benchmark(),
            "layer_metrics/readers/step_rows.py").window_rows
        obs["step_rows"] = rows(pathlib.Path(out_dir)) if out_dir \
            else ([], 0.0)
    return value(obs["step_rows"][0], params)

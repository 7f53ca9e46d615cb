"""Required operations and bytes of ONE DECODE STEP of the hybrid
state-space / attention / routed-expert stack (the program
serving._decode_step, one token for every seated slot), from the
sizes its model module puts in ``obs["dims"]`` (benchmark/models/
hybrid_ssm_moe.py: ``params`` by what a step reads of them, ``n_kind``,
``slot_state_bytes``, ``kv_bytes_per_token``) and from the engine's own
``serve_step`` rows of the traced slice.

Per step, with ``slots`` seated slots, ``tokens`` cached tokens over
them and ``hit`` (layer, expert) pairs of held experts that at least
one row chose (the rows' ``experts_hit``):

  bytes  every weight the step must read once, in 2 bytes: the
         state-space and attention mixers, each routed layer's router
         and shared expert, the head, and of the held experts ONLY
         those hit; each seated slot's state-space state and
         convolution tail read and written (2 x slot_state_bytes);
         the live K/V read (tokens x kv_bytes_per_token); one
         embedding row a slot. An idle slot's state, an expert nobody
         chose and the K/V past a slot's length are not required work.
  flops  2 x (the dense parameters x slots + an expert's parameters x
         the pairs computed here)

The step is memory-bound by far (a few rows an expert)."""

import pathlib

from benchmark import spec


def step_work(dims: dict, slots: float, tokens: float, hit: float,
              pairs: float) -> dict:
    count, params = dims["n_kind"], dims["params"]
    always = (count["ssm"] * params["ssm"]
              + count["attn"] * params["attn"]
              + count["experts"] * params["experts_always"]
              + params["head"])
    return {"flops": 2.0 * (always * slots + params["expert"] * pairs),
            "bytes": 2.0 * (always + params["expert"] * hit
                            + dims["d_model"] * slots)
            + 2.0 * dims["slot_state_bytes"] * slots
            + dims["kv_bytes_per_token"] * tokens}


def slice_rows(obs) -> list:
    """The engine's rows (layer_metrics/readers/step_rows.py reads
    them) whose step began inside the traced slice and landed a decode
    step of a routed model; both clocks are monotonic."""
    profile, out_dir = obs.get("profile"), obs.get("out_dir")
    if not profile or not out_dir:
        return []
    if "step_rows" not in obs:
        obs["step_rows"] = spec.load_module(
            spec.ROOT, spec.load_benchmark(),
            "layer_metrics/readers/step_rows.py").window_rows(
                pathlib.Path(out_dir))
    return [row for row in obs["step_rows"][0]
            if profile["started"] <= row["mono_start"]
            < profile["stopped"] and row.get("expert_pairs_chosen")]


def work(obs, calls):
    """Total over the traced slice: the mean step's work, from the
    rows of the slice, times the launches seen."""
    rows = slice_rows(obs)
    n_calls = sum(calls.values())
    if not rows or not n_calls:
        return None
    dims = obs["dims"]
    top_k = dims["top_k"] * dims["n_kind"]["experts"]

    def mean(values):
        values = list(values)
        return sum(values) / len(values)

    # a row describes the state its call DISPATCHED from and the
    # counters of the step it LANDED (the one before): one step apart,
    # which a mean over the slice does not see
    one = step_work(
        dims,
        slots=mean(row["expert_pairs_chosen"] / top_k for row in rows),
        tokens=mean(row["live_tokens"] for row in rows),
        hit=mean(row["experts_hit"] for row in rows),
        pairs=mean(row["expert_pairs_here"] for row in rows))
    return {"flops": one["flops"] * n_calls,
            "bytes": one["bytes"] * n_calls}

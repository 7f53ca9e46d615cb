"""Required operations and bytes of a PREFILL's attention over latent
rows in the expanded form (batch_shipyard_tpu/ops/attention.py,
cached_prefill_attention_kernel with values of a depth of their own:
the device events ``flash_prefill_cached``), as
transformer.LatentAttention runs it: every head's keys (nope + rope
lanes) and values (v lanes) multiplied out of the cached rows, causal.

Per prefill of n prompt tokens, in ONE attention layer, H heads:

  flops  a score over nope + rope lanes and a weighted sum over v
         lanes for every visible (query, key) pair:
         2 * H * (nope + rope + v) * n * (n + 1) / 2
  bytes  the queries and every head's keys read once, the values read
         once, the outputs written once, in 2 bytes:
         2 * n * H * (2 * (nope + rope) + 2 * v)

times the model's attention layers (the stack's and each
multi-token-prediction module's). n is the prompt's OWN tokens
(``tokens`` of the engine's ``landed`` prefill entries on its
``serve_step`` rows), not its bucket: padding, the lanes a key is laid
out at beyond nope + rope (256 for 192), and the keys a later segment
of the same bucket reads again are not required work. The prefills
counted are those whose launch lies inside the traced slice (as
layer_metrics/readers/launch_rows.against_the_trace picks them); one
that straddles an edge is in the events for its part and in the work
whole or not at all. A program that lands no such entries reads
None."""

import pathlib

from benchmark import spec


def prefill_work(tokens: float, layers: int, n_heads: int, nope: int,
                 rope: int, v_dim: int) -> dict:
    return {"flops": layers * 2.0 * n_heads * (nope + rope + v_dim)
            * tokens * (tokens + 1) / 2,
            "bytes": layers * 2.0 * tokens * n_heads
            * (2 * (nope + rope) + 2 * v_dim)}


def slice_prefills(obs) -> list:
    """The ``landed`` prefill entries of the engine's rows whose launch
    lies inside the traced slice."""
    profile, out_dir = obs.get("profile"), obs.get("out_dir")
    if not profile or not out_dir:
        return []
    if "step_rows" not in obs:
        obs["step_rows"] = spec.load_module(
            spec.ROOT, spec.load_benchmark(),
            "layer_metrics/readers/step_rows.py").window_rows(
                pathlib.Path(out_dir))
    return [launch for row in obs["step_rows"][0]
            for launch in row.get("landed", ())
            if launch["kind"] == "prefill" and "tokens" in launch
            and profile["started"] <= launch["landed_at"]
            - launch["period_ms"] / 1e3
            and launch["landed_at"] <= profile["stopped"]]


def work(obs, calls):
    """Total over the traced slice: each prefill inside it, over all
    attention layers."""
    prefills = slice_prefills(obs)
    if not prefills or not sum(calls.values()):
        return None
    dims = obs["dims"]
    layers = dims["n_kind"]["attn_full"] + dims["mtp_modules"]
    total = {"flops": 0.0, "bytes": 0.0}
    for launch in prefills:
        one = prefill_work(launch["tokens"], layers, dims["n_heads"],
                           dims["nope"], dims["rope"], dims["v_dim"])
        for name in total:
            total[name] += one[name]
    return total

"""Required operations and bytes of ONE DECODE STEP (the program
serving._decode_step, one token for every seated slot) of a stack of
full and sliding-window attention layers over routed experts, every
expert held. kernels/decode_step_kinds.py reads ONE
``kv_bytes_per_token`` for all layers; here the K/V a step must read
is by the layer's kind. The sizes come from ``obs["dims"]`` as the
model module gives them (benchmark/models/window_moe.py):

  n_kind                    {"attn_full", "attn_window", "experts"}
  params                    "attn" (one attention block), "experts_always"
                            (one router), "expert" (ONE expert, its
                            three matrices), "head"
  kv_bytes_per_token_layer  K and V rows of one cached token in ONE
                            attention layer
  n_heads, d_head, d_model, top_k

and the counts from the engine's own ``serve_step`` rows of the traced
slice (kernels/decode_step.py ``slice_rows``): the counters of the
decode step a call landed (``experts_hit``, ``expert_pairs_here``) and
the state it dispatched the next one from (``slots_active``,
``kv_tokens_full``, ``kv_tokens_window``).

Per step, with ``slots`` seated slots, ``hit`` (layer, expert) pairs
that at least one row chose, ``pairs`` (row, choice) pairs computed:

  bytes  every weight the step must read once, in 2 bytes: each
         attention block's four projections, each router, the head,
         and of the experts ONLY those hit; one embedding row a slot;
         the K/V the masks admit: kv_tokens_full a full layer,
         kv_tokens_window a window layer (keys behind a window, the
         rest of a slot's last page and an expert nobody chose are not
         required work)
  flops  2 x (the always-read parameters x slots + an expert's
         parameters x pairs) + attention's 4 x H x D a key attended

The step is memory-bound by far. A program that writes no window
attrs reads None."""

from benchmark import spec


def step_work(dims: dict, slots: float, hit: float, pairs: float,
              full: float, window: float) -> dict:
    params, kinds = dims["params"], dims["n_kind"]
    attn_layers = kinds["attn_full"] + kinds["attn_window"]
    always = (params["head"] + attn_layers * params["attn"]
              + kinds["experts"] * params["experts_always"])
    keys = kinds["attn_full"] * full + kinds["attn_window"] * window
    return {"flops": 2.0 * (always * slots + params["expert"] * pairs)
            + 4.0 * dims["n_heads"] * dims["d_head"] * keys,
            "bytes": 2.0 * (always + params["expert"] * hit
                            + dims["d_model"] * slots)
            + dims["kv_bytes_per_token_layer"] * keys}


def mean_step(obs) -> dict:
    rows = [row for row in spec.load_module(
        spec.ROOT, spec.load_benchmark(),
        "kernels/decode_step.py").slice_rows(obs)
        if "kv_tokens_window" in row]
    if not rows:
        return {}

    def mean(name):
        return sum(row[name] for row in rows) / len(rows)

    return {"slots": mean("slots_active"), "hit": mean("experts_hit"),
            "pairs": mean("expert_pairs_here"),
            "full": mean("kv_tokens_full"),
            "window": mean("kv_tokens_window")}


def work(obs, calls):
    """Total over the traced slice: the mean step's work times the
    launches seen."""
    step = mean_step(obs)
    n_calls = sum(calls.values())
    if not step or not n_calls:
        return None
    one = step_work(obs["dims"], **step)
    return {"flops": one["flops"] * n_calls,
            "bytes": one["bytes"] * n_calls}

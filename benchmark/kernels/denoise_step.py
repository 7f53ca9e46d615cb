"""Required operations and bytes of ONE BLOCK PASS (the program
serving._denoise_or_commit under the name _decode_step: for every
seated slot its open block of ``block`` positions through the stack,
against the cached blocks before it, then the head, each position's
best token and its confidence, and the unmask choice) of a stack of
full attention layers over softmax-routed experts, all held. The sizes
come from ``obs["dims"]`` as the model module gives them
(benchmark/models/moe_block_diffusion.py):

  n_kind                    {"attn_full", "experts"}: the blocks
  block                     the positions a slot a pass
  params                    "attn", "experts_always" (one router),
                            "expert" (ONE expert, three matrices),
                            "head"
  kv_bytes_per_token_layer  K and V rows of one cached token in ONE
                            attention layer
  n_heads, d_head, d_model

and the counts from the engine's own ``serve_step`` rows of the traced
slice (kernels/decode_step.py ``slice_rows``): the counters of the
pass a call landed (``experts_hit``, ``expert_pairs_here``: over all
routed blocks and all positions) and the state it dispatched the next
one from (``slots_active``; ``live_tokens``: the keys a pass attends
over the seated slots, the open block's own rows among them).

Per pass, with ``slots`` seated slots, ``hit`` (layer, expert) pairs
that at least one row chose, ``pairs`` (row, choice) pairs computed,
``keys`` keys attended a layer:

  bytes  every weight the pass must read once, in 2 bytes: each
         attention block's four projections, each router, of the
         experts ONLY those hit, and the head ONCE for all ``block``
         positions; ``block`` embedding rows a slot; the live K/V of
         every layer, read ONCE for the block's queries
  flops  2 x (the always-read parameters x block x slots + an expert's
         parameters x pairs) + attention's 4 x H x D a key attended a
         position

A commit pass and a denoise pass are the same program and the same
work. The pass is memory-bound (24 rows an expert). A program that
writes no such rows reads None."""

from benchmark import spec


def step_work(dims: dict, slots: float, hit: float, pairs: float,
              keys: float) -> dict:
    params, kinds, block = dims["params"], dims["n_kind"], dims["block"]
    always = (params["head"] + kinds["attn_full"] * params["attn"]
              + kinds["experts"] * params["experts_always"])
    return {"flops": 2.0 * (always * block * slots
                            + params["expert"] * pairs)
            + 4.0 * dims["n_heads"] * dims["d_head"] * block
            * kinds["attn_full"] * keys,
            "bytes": 2.0 * (always + params["expert"] * hit
                            + block * dims["d_model"] * slots)
            + dims["kv_bytes_per_token_layer"] * kinds["attn_full"]
            * keys}


def mean_step(obs) -> dict:
    """The mean pass of the traced slice, from its rows: {"slots",
    "hit", "pairs", "keys"}, or {} without rows or attrs."""
    rows = [row for row in spec.load_module(
        spec.ROOT, spec.load_benchmark(),
        "kernels/decode_step.py").slice_rows(obs)
        if "block_commit_passes" in row and row.get("slots_active")]
    if not rows:
        return {}

    def mean(name):
        return sum(row[name] for row in rows) / len(rows)

    return {"slots": mean("slots_active"), "hit": mean("experts_hit"),
            "pairs": mean("expert_pairs_here"),
            "keys": mean("live_tokens")}


def work(obs, calls):
    """Total over the traced slice: the mean pass's work times the
    launches seen."""
    step = mean_step(obs)
    n_calls = sum(calls.values())
    if not step or not n_calls:
        return None
    one = step_work(obs["dims"], **step)
    return {name: one[name] * n_calls for name in ("flops", "bytes")}

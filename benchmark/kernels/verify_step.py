"""Required operations and bytes of ONE VERIFY-AND-DRAFT STEP (the
program serving._verify_and_draft: for every seated slot the pending
token and the draft through the stack at two positions, the accept,
the multi-token-prediction module over both positions, the next draft)
of a stack that opens with a dense feed-forward layer, then full and
sliding-window attention layers over routed experts of which a share
is held, with one such module behind it. kernels/
decode_step_windowed.py's count at ``positions`` = 1 + drafts query
positions a slot, plus the module's. The sizes come from
``obs["dims"]`` as the model module gives them (benchmark/models/
window_moe_mtp.py):

  n_kind                    {"attn_full", "attn_window", "mlp",
                            "experts"}: the STACK's blocks
  mtp_modules, drafts       the modules behind it (each one full
                            attention block and one routed block more,
                            and a projection), the drafts a step
                            verifies
  params                    "attn", "mlp", "experts_always" (one router
                            and one shared expert), "expert" (ONE
                            expert, three matrices), "head", "mtp_proj"
  kv_bytes_per_token_layer  K and V rows of one cached token in ONE
                            attention layer
  n_heads, d_head, d_model

and the counts from the engine's own ``serve_step`` rows of the traced
slice (kernels/decode_step.py ``slice_rows``): the counters of the
step a call landed (``experts_hit``, ``expert_pairs_here``: over the
stack's routed blocks and the module's, both positions) and the state
it dispatched the next one from (``slots_active``, ``kv_tokens_full``,
``kv_tokens_window``: the keys the FIRST position sees; the second
sees one more, its own).

Per step, with ``slots`` seated slots, ``hit`` (layer, expert) pairs
that at least one row chose, ``pairs`` (row, choice) pairs computed:

  bytes  every weight the step must read once, in 2 bytes: each
         attention block's four projections (the module's too), the
         dense layer, each router and shared expert, the module's
         projection, of the experts ONLY those hit, and THE HEAD TWICE
         (the module's input is the token the stack's logits choose:
         the head's second pass cannot begin before its first has
         ended, and the head does not fit on the chip between them);
         2 x positions embedding rows a slot; the K/V the masks admit,
         read ONCE for both positions: kv_tokens_full + slots a full
         layer (the stack's and the module's), kv_tokens_window + slots
         a window layer
  flops  2 x (the always-read parameters x positions x slots + an
         expert's parameters x pairs) + attention's 4 x H x D a key
         attended a position

The step is memory-bound by far. A program that writes no window
attrs reads None."""

from benchmark import spec


def step_work(dims: dict, slots: float, hit: float, pairs: float,
              full: float, window: float) -> dict:
    params, kinds = dims["params"], dims["n_kind"]
    modules = dims["mtp_modules"]
    positions = 1 + dims["drafts"]
    full_layers = kinds["attn_full"] + modules
    routed = kinds["experts"] + modules
    always = ((1 + modules) * params["head"]
              + (full_layers + kinds["attn_window"]) * params["attn"]
              + kinds["mlp"] * params["mlp"]
              + routed * params["experts_always"]
              + modules * params["mtp_proj"])
    keys = full_layers * (full + slots) \
        + kinds["attn_window"] * (window + slots)
    return {"flops": 2.0 * (always * positions * slots
                            + params["expert"] * pairs)
            + 4.0 * dims["n_heads"] * dims["d_head"] * keys * positions,
            "bytes": 2.0 * (always + params["expert"] * hit
                            + (1 + modules) * positions
                            * dims["d_model"] * slots)
            + dims["kv_bytes_per_token_layer"] * keys}


def mean_step(obs) -> dict:
    rows = [row for row in spec.load_module(
        spec.ROOT, spec.load_benchmark(),
        "kernels/decode_step.py").slice_rows(obs)
        if "kv_tokens_window" in row and "mtp_drafted" in row]
    if not rows:
        return {}

    def mean(name):
        return sum(row[name] for row in rows) / len(rows)

    return {"slots": mean("slots_active"), "hit": mean("experts_hit"),
            "pairs": mean("expert_pairs_here"),
            "full": mean("kv_tokens_full"),
            "window": mean("kv_tokens_window")}


def slice_work(step_work, obs, calls):
    """Total over the traced slice: ``step_work`` of the mean step
    times the launches seen (kernels/mtp_step.py counts its part of
    the same step by it)."""
    step = mean_step(obs)
    n_calls = sum(calls.values())
    if not step or not n_calls:
        return None
    one = step_work(obs["dims"], **step)
    return {name: one[name] * n_calls for name in ("flops", "bytes")}


def work(obs, calls):
    return slice_work(step_work, obs, calls)

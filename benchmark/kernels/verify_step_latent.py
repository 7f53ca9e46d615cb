"""Required operations and bytes of ONE VERIFY-AND-DRAFT STEP (the
program serving._verify_and_draft: for every seated slot the pending
token and the draft through the stack at two positions, the accept,
the multi-token-prediction module over both positions, the next draft)
of a stack of LATENT attention layers (one row of kv_rank + rope lanes
a cached token a layer, read by the absorbed form) over leading dense
feed-forward layers and routed experts of which a share is held, with
one such module behind it. The sizes come from ``obs["dims"]`` as the
model module gives them (benchmark/models/latent_moe_mtp.py):

  n_kind                    {"attn_full", "mlp", "experts"}: the
                            STACK's blocks (every attention layer is
                            full)
  mtp_modules, drafts       the modules behind it (each one attention
                            block and one routed block more, and a
                            projection), the drafts a step verifies
  params                    "attn" (both low ranks with their
                            up-projections, which the absorbed form
                            reads as it reads any weight, and the
                            output projection), "mlp", "experts_always"
                            (one router and one shared expert),
                            "expert" (ONE expert, three matrices),
                            "head", "mtp_proj"
  row_lanes, kv_rank        the lanes of a cached row that hold the
                            model's numbers (its c, then the rotary
                            key), and of them the value's
  n_heads, d_model

and the counts from the engine's own ``serve_step`` rows of the traced
slice (kernels/decode_step.py ``slice_rows``): the counters of the
step a call landed (``experts_hit``, ``expert_pairs_here``: over the
stack's routed blocks and the module's, both positions) and the state
it dispatched the next one from (``slots_active``, ``kv_tokens_full``:
the keys the FIRST position sees; the second sees one more, its own).

Per step, with ``slots`` seated slots, ``hit`` (layer, expert) pairs
that at least one row chose, ``pairs`` (row, choice) pairs computed:

  bytes  every weight the step must read once, in 2 bytes: each
         attention block's projections (the module's too), the dense
         layer, each router and shared expert, the module's projection,
         of the experts ONLY those hit, and THE HEAD TWICE (the
         module's input is the token the stack's logits choose: the
         head's second pass cannot begin before its first has ended,
         and the head does not fit on the chip between them);
         2 x positions embedding rows a slot; the latent rows the
         masks admit, read ONCE for both positions and for scores and
         values alike: (kv_tokens_full + slots) x row_lanes x 2 a layer
  flops  2 x (the always-read parameters x positions x slots + an
         expert's parameters x pairs) + the absorbed attention's
         2 x H x (row_lanes + kv_rank) a key attended a position

A row's padding to whole lane tiles (640 lanes stored for 576) is not
required work. A program that writes no such attrs reads None."""

from benchmark import spec


def step_work(dims: dict, slots: float, hit: float, pairs: float,
              full: float) -> dict:
    params, kinds = dims["params"], dims["n_kind"]
    modules = dims["mtp_modules"]
    positions = 1 + dims["drafts"]
    layers = kinds["attn_full"] + modules
    routed = kinds["experts"] + modules
    always = ((1 + modules) * params["head"] + layers * params["attn"]
              + kinds["mlp"] * params["mlp"]
              + routed * params["experts_always"]
              + modules * params["mtp_proj"])
    keys = layers * (full + slots)
    return {"flops": 2.0 * (always * positions * slots
                            + params["expert"] * pairs)
            + 2.0 * dims["n_heads"] * (
                dims["row_lanes"] + dims["kv_rank"]) * keys * positions,
            "bytes": 2.0 * (always + params["expert"] * hit
                            + (1 + modules) * positions
                            * dims["d_model"] * slots)
            + 2.0 * dims["row_lanes"] * keys}


def mean_step(obs) -> dict:
    rows = [row for row in spec.load_module(
        spec.ROOT, spec.load_benchmark(),
        "kernels/decode_step.py").slice_rows(obs)
        if "kv_tokens_full" in row and "mtp_drafted" in row]
    if not rows:
        return {}

    def mean(name):
        return sum(row[name] for row in rows) / len(rows)

    return {"slots": mean("slots_active"), "hit": mean("experts_hit"),
            "pairs": mean("expert_pairs_here"),
            "full": mean("kv_tokens_full")}


def work(obs, calls):
    """Total over the traced slice: the mean step's work times the
    launches seen."""
    step = mean_step(obs)
    n_calls = sum(calls.values())
    if not step or not n_calls:
        return None
    one = step_work(obs["dims"], **step)
    return {name: one[name] * n_calls for name in ("flops", "bytes")}

"""Required operations and bytes of ONE DECODE STEP (the program
serving._decode_step, one token for every seated slot) of a stack
built from a list of block kinds, WHATEVER the kinds: the sizes come
from ``obs["dims"]`` as the configuration's model module gives them:

  n_kind              {kind: blocks of that kind}, "experts" among them
  params              {kind: parameters a step reads of ONE such block}
                      for every kind but "experts", for which
                      "experts_always" (router and shared expert) and
                      "expert" (ONE held expert, all its matrices);
                      "head"
  slot_state_bytes    what a seated slot keeps beside its K/V, over
                      all blocks (a fixed-size state, a convolution's
                      tail)
  kv_bytes_per_token, d_model, top_k

and the counts from the engine's own ``serve_step`` rows of the traced
slice, as kernels/decode_step.py reads them (its ``slice_rows``).

Per step, with ``slots`` seated slots, ``tokens`` cached tokens over
them and ``hit`` (layer, expert) pairs of held experts that at least
one row chose:

  bytes  every weight the step must read once, in 2 bytes: each
         block's mixer, each routed block's router and shared expert,
         the head, and of the held experts ONLY those hit; each seated
         slot's state and tails read and written (2 x
         slot_state_bytes); the live K/V read; one embedding row a
         slot. An idle slot's state, an expert nobody chose and the
         K/V past a slot's length are not required work.
  flops  2 x (the always-read parameters x slots + an expert's
         parameters x the pairs computed here)

The step is memory-bound by far (a few rows an expert)."""

from benchmark import spec


def step_work(dims: dict, slots: float, tokens: float, hit: float,
              pairs: float) -> dict:
    params = dims["params"]
    always = params["head"] + sum(
        blocks * params["experts_always" if kind == "experts" else kind]
        for kind, blocks in dims["n_kind"].items())
    return {"flops": 2.0 * (always * slots + params["expert"] * pairs),
            "bytes": 2.0 * (always + params["expert"] * hit
                            + dims["d_model"] * slots)
            + 2.0 * dims["slot_state_bytes"] * slots
            + dims["kv_bytes_per_token"] * tokens}


def mean_step(obs) -> dict:
    """The mean decode step the traced slice landed, from its rows:
    {"slots", "tokens", "hit", "pairs"}, or {} without rows. A row
    describes the state its call DISPATCHED from and the counters of
    the step it LANDED (the one before): one step apart, which a mean
    over the slice does not see."""
    rows = spec.load_module(
        spec.ROOT, spec.load_benchmark(),
        "kernels/decode_step.py").slice_rows(obs)
    if not rows:
        return {}
    dims = obs["dims"]
    chosen_a_slot = dims["top_k"] * dims["n_kind"]["experts"]

    def mean(values):
        values = list(values)
        return sum(values) / len(values)

    return {
        "slots": mean(row["expert_pairs_chosen"] / chosen_a_slot
                      for row in rows),
        "tokens": mean(row["live_tokens"] for row in rows),
        "hit": mean(row["experts_hit"] for row in rows),
        "pairs": mean(row["expert_pairs_here"] for row in rows)}


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

"""Required operations and bytes of paged decode attention
(batch_shipyard_tpu/ops/paged_attention.py): one new query token per
slot attending over that slot's cached keys and values.

Per call (one layer, one engine step) with ``tokens`` live cached
tokens summed over the slots, H heads of depth D, and KV stored in
``kv_bytes`` bytes:

  bytes  K and V of every live token read once: 2 * tokens * H*D *
         kv_bytes, plus the queries read and the outputs written
         (2 * slots * H*D * 2 bytes)
  flops  scores and weighted values: 2 matmuls of one row against
         [tokens, D] per head: 4 * tokens * H * D

Whole pages are what the kernel moves; the tokens past a slot's length
in its last page are not required work and are not counted."""


def call_work(tokens: int, slots: int, n_heads: int, d_head: int,
              kv_bytes: int = 2) -> dict:
    width = n_heads * d_head
    return {"flops": 4.0 * tokens * width,
            "bytes": 2.0 * tokens * width * kv_bytes
            + 2.0 * slots * width * 2}


def work(obs, calls):
    """Total over the traced slice: the mean call's work, from the
    engine steps recorded inside the slice, times the calls seen."""
    steps = obs.get("traced_steps") or []
    n_calls = sum(calls.values())
    if not steps or not n_calls:
        return None
    dims = obs["dims"]
    slots = obs["counters"]["num_slots"]
    live = [s[5] for s in steps if s[2] > 0]    # steps that decoded
    if not live:
        return None
    mean_tokens = sum(live) / len(live)
    one = call_work(mean_tokens, slots, dims["n_heads"],
                    dims["d_head"])
    return {"flops": one["flops"] * n_calls,
            "bytes": one["bytes"] * n_calls}

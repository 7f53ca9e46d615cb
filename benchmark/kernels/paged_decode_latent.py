"""Required operations and bytes of paged decode attention over LATENT
rows in the absorbed form, at SEVERAL QUERY POSITIONS A SLOT
(batch_shipyard_tpu/ops/paged_attention.py,
mla_paged_decode_attention_kernel with q of ``positions`` = 1 + drafts
positions: a verify block).

Per call (one layer, one engine step) with ``tokens`` the keys the
FIRST position attends summed over the slots, H query heads, a cached
row of ``row_lanes`` lanes (the compressed vector c of ``kv_rank``
lanes, then the one rotary key) in 2 bytes:

  bytes  the rows of every key ANY position's mask admits, read ONCE
         for all positions and heads and for scores and values alike
         (the value is the row's first kv_rank lanes): position r sees
         one key more than position r - 1, so the union is
         tokens + drafts * slots keys: that * row_lanes * 2; plus the
         absorbed queries read (positions * slots * H * row_lanes * 2)
         and the weighted sums written (positions * slots * H *
         kv_rank * 2)
  flops  a score over row_lanes and a weighted sum over kv_rank, one
         row a query head a position:
         2 * (tokens + drafts * slots) * H * (row_lanes + kv_rank)
         * positions at most

``tokens`` is ``kv_tokens_full`` of the engine's own ``serve_step``
rows of the traced slice (every attention layer of such a model is
full, the module's too). Whole pages are what the kernel moves, and a
stored row is padded to whole lane tiles (640 lanes for 576): neither
the rest of a last page nor the padding is required work. A program
that writes no such attrs reads None."""

from benchmark import spec


def call_work(tokens: float, slots: float, drafts: int, n_heads: int,
              row_lanes: int, kv_rank: int) -> dict:
    positions = 1 + drafts
    keys = tokens + drafts * slots
    return {"flops": 2.0 * keys * n_heads * (row_lanes + kv_rank)
            * positions,
            "bytes": 2.0 * keys * row_lanes
            + 2.0 * positions * slots * n_heads * (row_lanes + kv_rank)}


def work(obs, calls):
    """Total over the traced slice: the calls seen are the kernel's
    over all attention blocks (the module's among them), each the mean
    call."""
    step = spec.load_module(
        spec.ROOT, spec.load_benchmark(),
        "kernels/verify_step_latent.py").mean_step(obs)
    n_calls = sum(calls.values())
    if not step or not n_calls:
        return None
    dims = obs["dims"]
    one = call_work(step["full"], step["slots"], dims["drafts"],
                    dims["n_heads"], dims["row_lanes"], dims["kv_rank"])
    return {name: one[name] * n_calls for name in ("flops", "bytes")}

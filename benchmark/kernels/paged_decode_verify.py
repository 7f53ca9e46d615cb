"""Required operations and bytes of grouped-query paged decode
attention at SEVERAL QUERY POSITIONS A SLOT (batch_shipyard_tpu/ops/
paged_attention.py, gqa_paged_decode_attention_kernel with q of
``positions`` = 1 + drafts positions: a verify block), over layers of
two kinds, full and sliding-window. kernels/paged_decode_windowed.py
counts one position.

Per call (one layer, one engine step) with ``tokens`` the keys the
FIRST position attends summed over the slots, H query heads over Hkv
K/V heads of depth D, K/V in 2 bytes:

  bytes  K and V rows of Hkv * D lanes of every key ANY position's
         mask admits, read ONCE for all positions: position r sees one
         key more than position r - 1 (its own), and in a window layer
         one fewer at the far end, so the union is tokens + drafts *
         slots keys: 2 * that * Hkv*D * 2; plus the queries read and
         the outputs written (2 * positions * slots * H*D * 2)
  flops  scores and weighted values, one row a query head a position:
         4 * (tokens + drafts * slots) * H * D * positions at most

``tokens`` is by the layer's kind, from the engine's own
``serve_step`` rows of the traced slice: ``kv_tokens_full`` for a full
layer (the stack's, and each multi-token-prediction module's, which
reads the same pool through the same tables), ``kv_tokens_window`` for
a window layer. Whole pages are what the kernel moves; the rest of a
last page and of a window's first page are not required work. A
program that writes no such attrs reads None."""

from benchmark import spec


def call_work(tokens: float, slots: float, drafts: int, n_heads: int,
              n_kv_heads: int, d_head: int) -> dict:
    positions = 1 + drafts
    keys = tokens + drafts * slots
    return {"flops": 4.0 * keys * n_heads * d_head * positions,
            "bytes": 2.0 * keys * n_kv_heads * d_head * 2
            + 2.0 * positions * slots * n_heads * d_head * 2}


def work(obs, calls):
    """Total over the traced slice: the calls seen are the kernel's
    over all attention blocks (the modules' among the full ones), full
    and window in the model's ratio; each kind's share of them times
    that kind's mean call."""
    step = spec.load_module(
        spec.ROOT, spec.load_benchmark(),
        "kernels/paged_decode_windowed.py").mean_step(obs)
    n_calls = sum(calls.values())
    if not step or not n_calls:
        return None
    dims = obs["dims"]
    layers = {"full": dims["n_kind"]["attn_full"] + dims["mtp_modules"],
              "window": dims["n_kind"]["attn_window"]}
    steps = n_calls / sum(layers.values())
    total = {"flops": 0.0, "bytes": 0.0}
    for kind, count in layers.items():
        one = call_work(step[kind], step["slots"], dims["drafts"],
                        dims["n_heads"], dims["n_kv_heads"],
                        dims["d_head"])
        for name in total:
            total[name] += one[name] * count * steps
    return total

"""Required operations and bytes of grouped-query paged decode
attention over layers of TWO kinds, full and sliding-window
(batch_shipyard_tpu/ops/paged_attention.py,
gqa_paged_decode_attention_kernel): one new query token per slot
attending over the keys and values that layer's mask admits.

Per call (one layer, one engine step) with ``tokens`` attended keys
summed over the slots, H query heads over Hkv K/V heads of depth D,
K/V in 2 bytes:

  bytes  K and V rows of Hkv * D lanes of every attended key read
         once: 2 * tokens * Hkv*D * 2, plus the queries read and the
         outputs written (2 * slots * H*D * 2)
  flops  scores and weighted values, one row a query head against
         [tokens, D]: 4 * tokens * H * D

``tokens`` is by the layer's kind, from the engine's own
``serve_step`` rows of the traced slice (the state each call
dispatched its decode step from): ``kv_tokens_full`` (every key a
seated slot holds) for a full layer, ``kv_tokens_window`` (each slot's
newest ``window`` at most) for a window layer. Whole pages are what
the kernel moves; the tokens a last page holds past a slot's length,
and those a first page holds before the window's edge, are not
required work and are not counted. A program that writes no such
attrs reads None."""

from benchmark import spec


def call_work(tokens: float, slots: float, n_heads: int,
              n_kv_heads: int, d_head: int) -> dict:
    return {"flops": 4.0 * tokens * n_heads * d_head,
            "bytes": 2.0 * tokens * n_kv_heads * d_head * 2
            + 2.0 * slots * n_heads * d_head * 2}


def mean_step(obs) -> dict:
    """The mean decode step the traced slice dispatched, from its
    rows: {"slots", "full", "window"} (seated slots; keys attended by
    ONE full and ONE window layer), or {} without rows or attrs."""
    rows = [row for row in spec.load_module(
        spec.ROOT, spec.load_benchmark(),
        "kernels/decode_step.py").slice_rows(obs)
        if "kv_tokens_window" in row and row.get("slots_active")]
    if not rows:
        return {}

    def mean(name):
        return sum(row[name] for row in rows) / len(rows)

    return {"slots": mean("slots_active"),
            "full": mean("kv_tokens_full"),
            "window": mean("kv_tokens_window")}


def work(obs, calls):
    """Total over the traced slice: the calls seen are the kernel's
    over all attention layers, full and window in the model's ratio;
    each kind's share of them times that kind's mean call."""
    step = mean_step(obs)
    n_calls = sum(calls.values())
    if not step or not n_calls:
        return None
    dims = obs["dims"]
    layers = {"full": dims["n_kind"]["attn_full"],
              "window": dims["n_kind"]["attn_window"]}
    steps = n_calls / sum(layers.values())
    total = {"flops": 0.0, "bytes": 0.0}
    for kind, count in layers.items():
        one = call_work(step[kind], step["slots"], dims["n_heads"],
                        dims["n_kv_heads"], dims["d_head"])
        for name in total:
            total[name] += one[name] * count * steps
    return total

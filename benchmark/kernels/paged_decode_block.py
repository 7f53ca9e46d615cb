"""Required operations and bytes of grouped-query paged decode
attention over A BLOCK WHOSE QUERIES ALL SEE ALL KEYS
(batch_shipyard_tpu/ops/paged_attention.py,
gqa_paged_decode_attention_kernel with q of ``block`` positions and
``causal`` False: a block denoised as one). kernels/
paged_decode_verify.py counts a verify block, whose query r is masked
to the keys up to its own.

Per call (one layer, one pass) with ``keys`` the keys attended summed
over the slots (the block's own rows among them), H query heads over
Hkv K/V heads of depth D, K/V in 2 bytes:

  bytes  K and V rows of Hkv * D lanes of every key, read ONCE for all
         the block's positions: 2 * keys * Hkv*D * 2; plus the queries
         read and the outputs written (2 * block * slots * H*D * 2)
  flops  scores and weighted values, one row a query head a position:
         4 * keys * H * D * block

``keys`` is ``live_tokens`` of the engine's own ``serve_step`` rows of
the traced slice (kernels/denoise_step.py ``mean_step``). Whole pages
are what the kernel moves; the rest of a last page is not required
work. A program that writes no such rows reads None."""

from benchmark import spec


def call_work(keys: float, slots: float, block: int, n_heads: int,
              n_kv_heads: int, d_head: int) -> dict:
    return {"flops": 4.0 * keys * n_heads * d_head * block,
            "bytes": 2.0 * keys * n_kv_heads * d_head * 2
            + 2.0 * block * slots * n_heads * d_head * 2}


def work(obs, calls):
    """Total over the traced slice: the calls seen are the kernel's
    over all attention layers, each the mean pass's call."""
    step = spec.load_module(
        spec.ROOT, spec.load_benchmark(),
        "kernels/denoise_step.py").mean_step(obs)
    n_calls = sum(calls.values())
    if not step or not n_calls:
        return None
    dims = obs["dims"]
    one = call_work(step["keys"], step["slots"], dims["block"],
                    dims["n_heads"], dims["n_kv_heads"], dims["d_head"])
    return {name: one[name] * n_calls for name in ("flops", "bytes")}

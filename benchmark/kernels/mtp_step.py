"""Required operations and bytes of THE MULTI-TOKEN-PREDICTION MODULE's
part of one verify-and-draft step (batch_shipyard_tpu/models/
transformer.py, MTPModule, as serving._verify_and_draft runs it: over
``positions`` = 1 + drafts positions of every seated slot, its
projection, one full attention block with K/V of its own, one routed
block over the held share of its own experts), from ``obs["dims"]``
(kernels/verify_step.py has the keys) and the traced slice's mean step
(verify_step.mean_step).

Per step, with ``slots`` seated slots:

  bytes  the module's weights once, in 2 bytes: the projection, the
         attention block, the router and shared expert, and of its
         experts ONLY those hit (the rows' ``experts_hit`` counts the
         stack's routed blocks and the module's together: the module's
         share is one block's of them); its layer's live K/V read once
         for both positions (kv_tokens_full + slots)
  flops  2 x the parameters read x the rows that read them, and
         attention's 4 x H x D a key attended a position

THE HEAD IS LEFT OUT, bytes and time: the module's logits go through
the stack's own lm_head, whose device event names the argument
``params__lm_head__`` and not ``params__mtp__``. The reader
(layer_metrics/readers/span_in_program_roofline.py) times the module
from the first to the last operation of a launch that names one of ITS
arguments, and the head's second pass comes behind the last of them;
counting its bytes against a span that leaves it out would read high.
The head's second pass is in kernels/verify_step.py's count."""

from benchmark import spec


def step_work(dims: dict, slots: float, hit: float, pairs: float,
              full: float, window: float) -> dict:
    del window      # the module's layer is full attention
    params, kinds = dims["params"], dims["n_kind"]
    positions = 1 + dims["drafts"]
    routed = kinds["experts"] + dims["mtp_modules"]
    always = (params["mtp_proj"] + params["attn"]
              + params["experts_always"])
    keys = full + slots
    return {"flops": 2.0 * (always * positions * slots
                            + params["expert"] * pairs / routed)
            + 4.0 * dims["n_heads"] * dims["d_head"] * keys * positions,
            "bytes": 2.0 * (always + params["expert"] * hit / routed)
            + dims["kv_bytes_per_token_layer"] * keys}


def work(obs, calls):
    """Total over the traced slice: the mean step's work times the
    decode launches seen."""
    return spec.load_module(
        spec.ROOT, spec.load_benchmark(),
        "kernels/verify_step.py").slice_work(step_work, obs, calls)

"""Required operations and bytes of the DELTA blocks of one decode
step (batch_shipyard_tpu/models/delta.py, one token for every seated
slot: projections, short convolution, the rank-1 read-modify-write of
the state, gate and output projection), from ``obs["dims"]``
(``n_kind["delta"]`` blocks of ``params["delta"]`` parameters each;
``slot_state_bytes``, which such a stack's slots hold for these blocks
alone; per block a state of delta_heads x delta_head_dim**2 entries a
slot) and the seated slots of the traced slice's mean step
(kernels/decode_step_kinds.py, ``mean_step``).

Per step, with ``slots`` seated slots:

  bytes  the blocks' weights once, in 2 bytes; each seated slot's
         state and convolution tails read ONCE and written ONCE
         (2 x slot_state_bytes). A second read of the state (the rule
         needs k^T S before it can write S) is the implementation's,
         not required work: a step that keeps a head's state on the
         chip between the two reads one.
  flops  2 x the blocks' parameters x slots, and 8 a state entry
         (decay, the key's read, the rank-1 write, the query's read)

Memory-bound by far."""

from benchmark import spec


def step_work(dims: dict, slots: float) -> dict:
    blocks = dims["n_kind"]["delta"]
    weights = blocks * dims["params"]["delta"]
    state = blocks * dims["delta_heads"] * dims["delta_head_dim"] ** 2
    return {"flops": (2.0 * weights + 8.0 * state) * slots,
            "bytes": 2.0 * weights
            + 2.0 * dims["slot_state_bytes"] * slots}


def work(obs, calls):
    """Total over the traced slice: the mean step's work times the
    decode launches seen."""
    step = spec.load_module(
        spec.ROOT, spec.load_benchmark(),
        "kernels/decode_step_kinds.py").mean_step(obs)
    n_calls = sum(calls.values())
    if not step or not n_calls:
        return None
    one = step_work(obs["dims"], step["slots"])
    return {"flops": one["flops"] * n_calls,
            "bytes": one["bytes"] * n_calls}

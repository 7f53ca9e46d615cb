"""The comparison that decides ``correct``, against the plain
reference, outside the timed window. Which reference is the
configuration's own matter: its model module
(benchmark/models/<model_module>.py) gives ``teacher_forced_logits``
from its file under benchmark/reference/, and nothing here names an
architecture.

The timed path yields TOKENS (greedy), not logits, and with seeded
random weights near-ties are common, so token equality is not a test.
EVERY request the window finished is run once through the float32
reference (prompt + served tokens, teacher-forced), and for every
served token the GAP is read: how far its reference logit lies below
the reference's best at that position. A served token that IS the
reference's best has gap 0.

The number held to a limit (configuration file, key ``check``;
PERF.md gives the readings it was set from) is

  gap_tail_mean   the mean over all served tokens of
                  max(0, gap - tail_from)

``tail_from`` (0.03 for the serve configuration) is what rounding
alone explains: the engine's decode logits are bfloat16, whose
spacing at the best logit's size (4 to 8) is 0.031, so a sound
engine's wrong picks are near-ties within about that, and few reach
beyond. Noise of another origin does, and a mean of the excess also
weighs how far. The control is the program itself with its own
lower-precision path switched on (``kv_cache_dtype="int8"``;
benchmark/calibrate.py --kv-int8 reads it at the cell's own load) and
must fail the limit. A token altered where it is produced lands on a
random vocabulary entry, a gap of several logits: ONE such token
among the ~13,000 of a window lifts the mean over the limit.
``gap_max`` and ``gap_mean`` are printed beside it, without a limit,
for the reader."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

SEQ_BUCKET = 256       # teacher-forced sequences are padded to these
ROW_BUCKET = 64        # ... and the rows read out to these


def _pad(n: int, bucket: int) -> int:
    return -(-n // bucket) * bucket


@jax.jit
def _row_readings(logits, picked):
    """Per row: how far the picked token's logit lies below the row's
    best, and the best itself."""
    best = jnp.max(logits, axis=-1)
    at = jnp.take_along_axis(logits, picked[:, None], axis=-1)[:, 0]
    return best - at, best


def serve_gaps(params, model_module, config: dict, dims: dict,
               finished: list) -> dict:
    """Teacher-force every finished request through the reference of
    the configuration's model module.
    -> {"gaps", "best": one entry per served token, "request": the
        request's idx per token, "requests": n}."""
    out = {"gaps": [], "best": [], "request": []}
    # longest first: the few large programs compile (or load) first
    for request in sorted(finished, key=lambda r: (
            -len(r["prompt"]) - len(r["tokens"]), r["idx"])):
        prompt, served = request["prompt"], request["tokens"]
        n = len(served)
        sequence = prompt + served[:-1]
        padded = _pad(len(sequence), SEQ_BUCKET)
        tokens = jnp.asarray(
            sequence + [0] * (padded - len(sequence)), jnp.int32)
        first = len(prompt) - 1
        rows = list(range(first, first + n))
        rows += [rows[-1]] * (_pad(n, ROW_BUCKET) - n)
        picked = jnp.asarray(served + [served[-1]] * (len(rows) - n),
                             jnp.int32)
        logits = model_module.teacher_forced_logits(
            params, tokens, jnp.asarray(rows, jnp.int32), config, dims)
        gaps, best = _row_readings(logits, picked)
        out["gaps"].extend(np.asarray(gaps)[:n].tolist())
        out["best"].extend(np.asarray(best)[:n].tolist())
        out["request"].extend([request["idx"]] * n)
    out["requests"] = len(finished)
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, list[str]]:
    """Each number that has a limit beside it; correct only if every
    one holds. A number missing or not finite fails."""
    lines, ok = [], True
    for name, limit in sorted(limits.items()):
        value = numbers.get(name)
        good = (value is not None and np.isfinite(value)
                and value <= limit)
        ok = ok and bool(good)
        lines.append(f"check {name}: {value!r} (limit <= {limit!r}) "
                     f"{'ok' if good else 'FAILED'}")
    return ok, lines


def gap_numbers(gaps: list, tail_from: float) -> dict:
    if not gaps:
        return {"gap_tail_mean": None, "gap_max": None,
                "gap_mean": None}
    gaps = np.asarray(gaps, np.float64)
    return {"gap_tail_mean": float(
                np.maximum(0.0, gaps - tail_from).mean()),
            "gap_max": float(gaps.max()),
            "gap_mean": float(gaps.mean())}

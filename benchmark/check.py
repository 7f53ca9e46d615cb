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
lower-precision path switched on (the configuration's
``check.control``, keyword overrides for the module's
``program_model``; benchmark/calibrate.py --control reads it at the
cell's own load, and --kv-int8 spells ``kv_cache_dtype="int8"``) and
must fail the limit. A token altered where it is produced lands on a
random vocabulary entry, a gap of several logits: ONE such token
among the ~13,000 of a window lifts the mean over the limit.
``gap_max`` and ``gap_mean`` are printed beside it, without a limit,
for the reader.

A MODEL THAT MAKES DISCRETE CHOICES (top-k routed experts). A
bfloat16 program and a float32 reference disagree about the k-th and
(k+1)-th of many close scores at some per cent of positions, whatever
the program does, and one such flip moves the logits by far more than
any limit above: the gap is blind there. So a model module MAY give

  decision_layers(config, dims) -> [(layer name, k, n), ...]

(the layers that choose k of n per position), and the check then
takes the TIMED PATH'S OWN choices, through one optional public method
of the engine, found with getattr:

  engine.take_decisions(request_id)
      -> None, or {"first": p, "layers": {layer name: int [m, k]}}

the choices the timed steps themselves computed at positions p ..
p+m-1 of the request's sequence (prompt, then served tokens): the
prefill's for the prompt positions it ran, each decode step's for the
position it fed. The engine hands the record over and forgets it.
Positions it did not compute (a prefix served from shared pages) have
no record: the reference takes its own choice there and
``positions_unrecorded`` says how many. The module's reference,
``teacher_forced_logits(..., decisions={name: int32 [T, k]})`` (a row
of -1: no record), selects the experts it is handed, weighs them by
ITS OWN scores, and returns beside the logits one SLACK per position
and layer: its own k-th best selection score less the lowest selection
score among the handed ones, 0 when the sets are equal. Nothing taken
from the program goes unjudged: the tokens by the gap, the choices by

  routing_rejected_share   the share of recorded (position, layer)
                           whose slack is above ``check.slack_from``

(``slack_from``: what bfloat16 explains, set from sound readings as
``tail_from`` was), held to ``check.limits`` like gap_tail_mean. A row
with an index out of range or twice is rejected outright. Printed
without a limit: ``routing_flip_share`` (slack > 0), ``slack_max``. A
finished request without a record makes the share None, which fails. A
module that declares nothing is judged as before, by the same code.

``check.served_tokens_at_most`` bounds the check's cost: the longest
finished request, then the others in an order drawn from the seed,
until that many served tokens are reached."""

from __future__ import annotations

import random

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


def _longest_first(request: dict):
    return (-len(request["prompt"]) - len(request["tokens"]),
            request["idx"])


def sample(finished: list, served_tokens_at_most, seed: int) -> list:
    """The finished requests the check reads, longest first (the few
    large programs compile, or load, first). Without a bound: all of
    them. With one: the longest, then the others in an order drawn
    from the seed, until that many served tokens are reached."""
    ordered = sorted(finished, key=_longest_first)
    if served_tokens_at_most is None or not ordered:
        return ordered
    rest = ordered[1:]
    random.Random(f"{int(seed)}/check-sample").shuffle(rest)
    taken, tokens = [], 0
    for request in ordered[:1] + rest:
        if tokens >= served_tokens_at_most:
            break
        taken.append(request)
        tokens += len(request["tokens"])
    return sorted(taken, key=_longest_first)


def controls(stated) -> list:
    """A ``control`` entry (a configuration's ``check.control``, a
    reference case's ``control``) as a list: it is one set of
    overrides or a list of them."""
    return [stated] if isinstance(stated, dict) else list(stated)


def _handed(record, layers: list, length: int, padded: int):
    """A request's record as the reference takes it: ({name: int32
    [padded, k]}, -1 where there is none; the recorded positions
    [padded] bool), or None for a record that is missing or not of the
    declared shape."""
    if not isinstance(record, dict) or not isinstance(
            record.get("layers"), dict):
        return None
    first = record.get("first")
    if not isinstance(first, (int, np.integer)) or \
            not 0 <= first <= length:
        return None
    out, counts = {}, set()
    for name, k, _n in layers:
        rows = np.asarray(record["layers"].get(name))
        if rows.ndim != 2 or rows.shape[1] != k or \
                not np.issubdtype(rows.dtype, np.integer):
            return None
        rows = rows[:length - first]
        counts.add(len(rows))
        out[name] = np.full((padded, k), -1, np.int32)
        out[name][first:first + len(rows)] = rows
    if len(counts) != 1:
        return None
    recorded = np.zeros((padded,), bool)
    recorded[first:first + counts.pop()] = True
    return out, recorded


def _malformed(rows, n: int):
    """Rows [T, k] with an index out of range or the same one twice:
    what no top-k could have chosen."""
    ordered = np.sort(rows, axis=-1)
    return ((rows < 0) | (rows >= n)).any(-1) | \
        (ordered[:, 1:] == ordered[:, :-1]).any(-1)


def reroute(record: dict, layers: list, share: float, seed: int) -> dict:
    """A control that corrupts the RECORD the harness was handed (the
    computation is untouched): that share of the recorded choices'
    last index is sent to another, drawn from the seed. It has to read
    over the routing_rejected_share limit, which shows that the
    admission of choices is not blind."""
    rng = np.random.default_rng([int(seed) & 0x7FFFFFFF, 0x5EED])
    out = {"first": record["first"], "layers": {}}
    for name, _k, n in layers:
        rows = np.array(record["layers"][name])
        hit = rng.random(len(rows)) < share
        rows[hit, -1] = (rows[hit, -1] + rng.integers(
            1, n, int(hit.sum()))) % n
        out["layers"][name] = rows
    return out


def serve_gaps(params, model_module, config: dict, dims: dict,
               finished: list, layers=(), served_tokens_at_most=None,
               seed: int = 0) -> dict:
    """Teacher-force the finished requests (all, or ``sample``'s)
    through the reference of the configuration's model module.
    -> {"gaps", "best": one entry per served token, "request": the
        request's idx per token, "requests": n read,
        "requests_finished": n}, and for a module that declares
    decision ``layers`` (spec.decision_layers), whose requests carry
    the engine's record under "decisions": "slack" (one entry per
    recorded position and layer), "positions", "positions_unrecorded",
    "requests_without_record"."""
    out = {"gaps": [], "best": [], "request": []}
    layers = list(layers)
    if layers:
        out.update(slack=[], positions=0, positions_unrecorded=0,
                   requests_without_record=0)
    taken = sample(finished, served_tokens_at_most, seed)
    for request in taken:
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
        if layers:
            logits = _forced_logits(
                out, params, model_module, config, dims, layers,
                request.get("decisions"), tokens, rows, len(sequence))
        else:
            logits = model_module.teacher_forced_logits(
                params, tokens, jnp.asarray(rows, jnp.int32), config,
                dims)
        gaps, best = _row_readings(logits, picked)
        out["gaps"].extend(np.asarray(gaps)[:n].tolist())
        out["best"].extend(np.asarray(best)[:n].tolist())
        out["request"].extend([request["idx"]] * n)
    out["requests"] = len(taken)
    out["requests_finished"] = len(finished)
    return out


def _forced_logits(out: dict, params, model_module, config, dims,
                   layers, record, tokens, rows, length: int):
    """The reference on the choices it is handed; their slacks and the
    counts go into ``out``."""
    padded = tokens.shape[0]
    handed = _handed(record, layers, length, padded)
    if handed is None:      # the reference takes its own choice
        out["requests_without_record"] += 1
        handed = _handed({"first": 0, "layers": {
            name: np.zeros((0, k), np.int32) for name, k, _n in layers}},
            layers, length, padded)
    decisions, recorded = handed
    refused = {}
    for name, _k, n in layers:
        refused[name] = recorded & _malformed(decisions[name], n)
        decisions[name][refused[name]] = -1
    logits, slacks = model_module.teacher_forced_logits(
        params, tokens, jnp.asarray(rows, jnp.int32), config, dims,
        decisions={name: jnp.asarray(value)
                   for name, value in decisions.items()})
    for name, _k, _n in layers:
        slack = np.array(slacks[name], np.float64)
        slack[refused[name]] = np.inf
        out["slack"].extend(slack[recorded].tolist())
    out["positions"] += length
    out["positions_unrecorded"] += length - int(recorded.sum())
    return logits


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


def routing_numbers(readings: dict, slack_from: float) -> dict:
    """The numbers of a module that declares decisions, from
    serve_gaps' readings. routing_rejected_share is None (and fails
    its limit) where a finished request came without a record, or
    nothing was recorded at all."""
    slack = np.asarray(readings["slack"], np.float64)
    judged = len(slack) and not readings["requests_without_record"]
    return {"routing_rejected_share": float(
                (slack > slack_from).mean()) if judged else None,
            "routing_flip_share": float((slack > 0).mean())
            if len(slack) else None,
            "slack_max": float(slack.max()) if len(slack) else None}

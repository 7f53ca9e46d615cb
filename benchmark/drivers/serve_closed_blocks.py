#!/usr/bin/env python3
"""Kind ``serve-closed-blocks``: the closed loop of drivers/serve.py
(its Session: set-up, warm-up, window, every end-to-end number) for a
model that generates by diffusion over blocks, with a check of its
own.

WHY ITS OWN CHECK. benchmark/check.py::serve_gaps is autoregressive by
construction: it teacher-forces ``prompt + served[:-1]``, reads row r
for token r + 1 and cuts every record at the sequence's length. A token
of a block is conditioned on the tokens of ITS OWN block that were
unmasked before it, the request's last token and the ones dropped
behind it included, and is scored by the logit at its own position in
the pass that unmasked it. So the reference here is handed, a request
at a time, everything the program conditioned on (the engine's record,
``ContinuousBatcher.take_decisions``: every committed block's tokens,
the pass that unmasked each position, the routed layers' choices of
every pass), by the one function the model module gives for it
(``request_readings``), and four numbers are held to the limits of
the configuration's ``check`` (PERF.md has the readings they were set
from):

  gap_tail_mean            the mean over ALL served tokens of
                           max(0, gap - tail_from), the gap read in
                           the reference's logits of the pass that
                           unmasked the token (check.gap_numbers)
  routing_rejected_share   the share of recorded (position, routed
                           entry) whose slack is above slack_from, over
                           the passes that wrote K/V and every denoise
                           pass (check.routing_numbers)
  routing_slack_tail_mean  the mean over the same choices of
                           max(0, slack - slack_from): what
                           gap_tail_mean is to the gaps, so that a
                           choice counts by how far it lies from the
                           reference's and not as one of 300,000
  unmask_rejected_share    the share of unmask choices (one a denoise
                           pass of a block) whose slack in
                           log-confidence is above unmask_slack_from

A finished request without a record, or with one that is not of the
declared shape or does not hold the served tokens, makes the routing and
unmask numbers None, which fails. Beside a program that lacks the mechanism
(the parent of the PR that brought this file) a run fails at once
with a SpecError, before any weight is made.

Controls (``check.control``): keyword overrides of the module's
program_model, as everywhere; under "decisions" a corruption of the
record before the reference sees it:
{"reroute_share": s} (check.reroute over the routed entries) and
{"shift_unmask_share": s} (that share of the generated positions named
as unmasked one pass later than they were).

Run as a script it is benchmark/calibrate.py (the same flags) with
this Session in the place of serve's: the cell's calibration."""

from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402

from benchmark import check, harness, spec  # noqa: E402
from benchmark.drivers import serve  # noqa: E402


def shift_unmask(record: dict, name: str, steps: int, share: float,
                 seed: int) -> dict:
    """A control that corrupts the RECORD (the computation is
    untouched): that share of the positions it names as unmasked by a
    denoise pass is named one pass later (the last pass: the first)."""
    rng = np.random.default_rng([int(seed) & 0x7FFFFFFF, 0x5EED + 1])
    at = np.array(record["layers"][name])
    hit = (at[:, 0] < steps) & (rng.random(len(at)) < share)
    at[hit, 0] = (at[hit, 0] + 1) % steps
    return {**record, "layers": {**record["layers"], name: at}}


class Session(serve.Session):
    """serve.Session with the block check: the record reaches ``check``
    as the engine handed it, and a control's corruption is applied
    there."""

    def __init__(self, ctx, control=None, build=serve.build_engine):
        # before any weight is made: beside a program without the
        # mechanism the model module says so, and the run fails at once
        lacks = spec.load_model(harness.merged(
            ctx.cell.config, ctx.tiny), ctx.root).program_lacks()
        if lacks:
            raise spec.SpecError(lacks)
        super().__init__(ctx, control=control, build=build)
        self.corruption, self.record_control = self.record_control, None

    def _corrupted(self, record, seed: int):
        spoil = self.corruption or {}
        if record is None or not spoil:
            return record
        if spoil.get("reroute_share"):
            routed = [layer for layer in self.decision_layers
                      if layer[0] != self.model_module.UNMASK]
            record = {**record, "layers": {
                **record["layers"], **check.reroute(
                    record, routed, float(spoil["reroute_share"]),
                    seed)["layers"]}}
        if spoil.get("shift_unmask_share"):
            record = shift_unmask(
                record, self.model_module.UNMASK, self.dims["steps"],
                float(spoil["shift_unmask_share"]), seed)
        return record

    def check(self, rows: list) -> dict:
        finished = [r for r in rows if r["in_window"] and r["ok"]]
        section = self.model["check"]
        t_check = time.monotonic()
        taken = check.sample(finished,
                             section.get("served_tokens_at_most"),
                             self.ctx.seed)
        out = {"gaps": [], "best": [], "request": [], "slack": [],
               "unmask_slack": [], "positions": 0,
               "positions_unrecorded": 0, "requests_without_record": 0,
               "requests": len(taken),
               "requests_finished": len(finished)}
        for request in taken:
            read = self.model_module.request_readings(
                self.params, request["prompt"], request["tokens"],
                self._corrupted(request.get("decisions"),
                                self.ctx.seed + request["idx"]),
                self.model, self.dims)
            if read is None:
                out["requests_without_record"] += 1
                continue
            out["gaps"] += read["gaps"]
            out["best"] += read["best"]
            out["request"] += [request["idx"]] * len(read["gaps"])
            for slack in read["slack"].values():
                out["slack"] += slack
            out["unmask_slack"] += read["unmask_slack"]
            out["positions"] += read["positions"]
            out["positions_unrecorded"] += read["positions_unrecorded"]
        numbers = check.gap_numbers(out["gaps"],
                                    float(section["tail_from"]))
        slack_from = float(section["slack_from"])
        numbers.update(check.routing_numbers(out, slack_from))
        numbers["routing_slack_tail_mean"] = None \
            if numbers["routing_rejected_share"] is None else float(
                np.maximum(0.0, np.asarray(out["slack"], np.float64)
                           - slack_from).mean())
        choices = np.asarray(out["unmask_slack"], np.float64)
        judged = len(choices) and not out["requests_without_record"]
        numbers["unmask_rejected_share"] = float(
            (choices > float(section["unmask_slack_from"])).mean()) \
            if judged else None
        numbers["unmask_slack_max"] = float(choices.max()) \
            if len(choices) else None
        return {"numbers": numbers, "requests": out["requests"],
                "tokens": len(out["gaps"]), "readings": out,
                "seconds": time.monotonic() - t_check}


def run(ctx, build=serve.build_engine) -> dict:
    session = Session(ctx, build=build)
    measured = session.window()
    # The reference check, outside the window. The pool is dropped
    # first so that the reference fits beside the weights.
    session.engine.cache = None
    session.engine = session.recorder = None
    checked = session.check(measured["rows"])
    with open(ctx.out_dir / "check_readings.json", "w",
              encoding="utf-8") as fh:
        json.dump(checked["readings"], fh)   # every reading, to look at
    numbers, readings = checked["numbers"], checked["readings"]
    limits = session.model["check"]["limits"]
    correct, lines = check.judge(numbers, limits)
    for line in lines:
        ctx.note(line)
    ctx.note(
        f"check: {checked['requests']} of "
        f"{readings['requests_finished']} finished requests "
        f"(check.served_tokens_at_most), {checked['tokens']} served "
        f"tokens, {len(readings['slack'])} routed choices and "
        f"{len(readings['unmask_slack'])} unmask choices against the "
        f"float32 reference in {checked['seconds']:.2f}s, each token in "
        f"the pass that unmasked it, on the timed path's own choices; "
        f"positions_unrecorded {readings['positions_unrecorded']} of "
        f"{readings['positions']}; without a limit: gap_max "
        f"{numbers['gap_max']!r}, gap_mean {numbers['gap_mean']!r}, "
        f"routing_flip_share {numbers['routing_flip_share']!r}, "
        f"slack_max {numbers['slack_max']!r}, unmask_slack_max "
        f"{numbers['unmask_slack_max']!r}")
    if readings["requests_without_record"]:
        ctx.note(f"check: NOT CORRECT: "
                 f"{readings['requests_without_record']} finished "
                 f"requests came without a record of the declared "
                 f"shape (take_decisions)")
    if not checked["requests"]:
        correct = False     # nothing finished: nothing was shown
    values = measured["values"]
    return {"correct": correct, "attempted": values["attempted"],
            "failed": values["failed"], "values": values,
            "compared": {name: {"value": numbers.get(name),
                                "limit": limit}
                         for name, limit in sorted(limits.items())},
            "memory_peak_bytes": measured["peak"],
            "obs": measured["obs"], "profile": measured["profile"]}


if __name__ == "__main__":
    from benchmark import calibrate
    serve.Session = Session
    sys.exit(calibrate.main())

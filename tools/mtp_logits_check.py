#!/usr/bin/env python3
"""The multi-token-prediction module's DRAFTS on the served path, held
against the float32 reference's module (PERF.md, PR 42): what
``correct`` does not see. The benchmark's check judges the tokens a
request is served (the stack's choices) and the routed choices of the
stack's layers and the module's; the module's own logits end in a
draft that is verified and, with weights from a seed, thrown away. Not
part of the benchmark.

A configuration of BENCHMARK.json whose model carries a module is
built as a run builds it (benchmark/drivers/serve.build_engine, the
seeded bfloat16 weights), a few requests are served, and after every
engine step each seated slot's pending token, position and draft are
read off the engine (``_tokens``, ``_positions``, ``_draft``: the
draft of the token AFTER the pending one at position p was made by
the module at x position p - 1, in the prefill for the first, in a
decode step for the others). Each finished request is then
teacher-forced through the reference (the module module's
``teacher_forced_logits(..., mtp_rows=...)``) and every draft's GAP is
read: how far its reference module logit lies below the reference
module's best at that position, the check's own number
(``gap_tail_mean`` = mean of max(0, gap - 0.03)) for the module's
tokens. The served tokens' own gaps are printed beside it.

``--lossless`` then serves the SAME requests through the same engine
built with the module left out (``program_model(mtp_modules=0)``) and
compares the tokens request by request. The two engines run two
compiled programs (a two-position verify, a one-position step), so on
a chip a near-tie may fall the other way, of two bfloat16 logits or
of a router's eighth and ninth score (which moves the logits by far
more than rounding does), and the requests part there. Each parted
request is judged at its first differing token, each engine's token
by the reference FORCED ONTO THAT ENGINE'S OWN routed choices
(``take_decisions``, as the benchmark's check forces it): ``gap`` of
the token below the reference's best. Both gaps within rounding say
both tokens are the stack's own choice under the choices each engine
made; a draft let through wrongly would land a random row, several
logits down.

    chiprun -- python3 tools/mtp_logits_check.py --seed 2147493500
    JAX_PLATFORMS=cpu python3 tools/mtp_logits_check.py --rehearse-tiny

One JSON line. Exit 1 where a number is not finite."""
import argparse
import json
import pathlib
import random
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config",
                        default="k-exaone-236b-a23b-serve-1chip")
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--requests", type=int, default=24)
    parser.add_argument("--new-tokens", type=int, default=48)
    parser.add_argument("--rehearse-tiny", action="store_true")
    parser.add_argument("--lossless", action="store_true")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from batch_shipyard_tpu.models.serving import Request
    from benchmark import check, harness, spec, weights
    from benchmark.drivers import serve

    model = harness.merged(spec.load_config(args.config, ROOT),
                           args.rehearse_tiny)
    module = spec.load_model(model, ROOT)
    dims = module.dims(model)
    params = weights.make_params(module.param_leaves(dims), args.seed,
                                 jnp.bfloat16)
    engine = serve.build_engine(module, model, params)
    if not engine.drafts:
        parser.error(f"{args.config}: its model carries no module")
    rng = random.Random(args.seed)
    longest = min(engine.max_decode_len - args.new_tokens - 2, 1536)
    prompts = {f"r{i}": [rng.randrange(1, dims["vocab"])
                         for _ in range(rng.randrange(
                             longest // 8, longest))]
               for i in range(args.requests)}
    for request_id, prompt in prompts.items():
        engine.submit(Request(request_id, prompt, args.new_tokens))
    # {request: {position of the drafted token: the draft}}
    drafts = {request_id: {} for request_id in prompts}
    done = {}
    while engine.pending():
        for request_id, tokens in engine.step():
            done[request_id] = tokens
        # settle, so that the device's state is the books'
        engine._settle("drain")
        positions = np.asarray(engine._positions)
        guessed = np.asarray(engine._draft)
        for i, slot in enumerate(engine._slots):
            if slot.request is not None:
                drafts[slot.request.request_id][
                    int(positions[i]) + 1] = int(guessed[i])
    engine.cache = None
    records = {request_id: engine.take_decisions(request_id)
               for request_id in prompts} if args.lossless else {}
    served, module_gaps, agree = [], [], 0
    for request_id, prompt in prompts.items():
        tokens = done[request_id]
        sequence = prompt + tokens
        # a draft of position q was made at x position q - 2
        judged = sorted(q for q in drafts[request_id]
                        if q - 1 < len(sequence))
        padded = -(-len(sequence) // check.SEQ_BUCKET) \
            * check.SEQ_BUCKET
        forced = jnp.asarray(sequence + [0] * (padded - len(sequence)),
                             jnp.int32)
        rows = list(range(len(prompt) - 1, len(sequence) - 1))
        logits, module_logits = module.teacher_forced_logits(
            params, forced, jnp.asarray(rows, jnp.int32), model, dims,
            mtp_rows=jnp.asarray([q - 2 for q in judged], jnp.int32))
        gaps, _best = check._row_readings(
            logits, jnp.asarray(tokens, jnp.int32))
        served.extend(np.asarray(gaps).tolist())
        picked = jnp.asarray([drafts[request_id][q] for q in judged],
                             jnp.int32)
        gaps, _best = check._row_readings(module_logits, picked)
        module_gaps.extend(np.asarray(gaps).tolist())
        agree += sum(drafts[request_id][q] == sequence[q]
                     for q in judged if q < len(sequence))
    tail_from = float(model["check"]["tail_from"])
    line = {"config": args.config, "seed": args.seed,
            "device": jax.devices()[0].device_kind,
            "requests": len(prompts), "served_tokens": len(served),
            "drafts_judged": len(module_gaps),
            "drafts_equal_to_the_served_token": agree,
            "served": check.gap_numbers(served, tail_from),
            "module": check.gap_numbers(module_gaps, tail_from),
            "module_best_share": float(np.mean(
                np.asarray(module_gaps) == 0.0))}
    if args.lossless:
        line["lossless"] = _without_the_module(
            args, module, model, dims, params, prompts, done, records)
    print(json.dumps(line))
    finite = all(np.isfinite(v) for part in ("served", "module")
                 for v in line[part].values())
    return 0 if finite else 1


def _without_the_module(args, module, model, dims, params, prompts,
                        done, records) -> dict:
    """The same requests through the engine built with the module
    left out, and where a request's tokens part from ``done``'s, each
    engine's token's gap at that position under the reference forced
    onto that engine's own routed choices (``records``: the drafting
    engine's)."""
    import jax.numpy as jnp
    import numpy as np

    from batch_shipyard_tpu.models.serving import Request
    from benchmark import check, spec
    from benchmark.drivers import serve

    layers = spec.decision_layers(module, model, dims)

    def gap(record, sequence, token) -> float:
        """``token``'s gap behind ``sequence`` under ``record``'s
        choices (the engine without a module records none for it: the
        reference then takes its own there, which no stack logit
        reads)."""
        padded = -(-len(sequence) // check.SEQ_BUCKET) \
            * check.SEQ_BUCKET
        for name, k, _n in layers:
            record["layers"].setdefault(name, np.full(
                (len(sequence) - record["first"], k), -1, np.int32))
        counts = {"slack": [], "positions": 0,
                  "positions_unrecorded": 0,
                  "requests_without_record": 0}
        logits = check._forced_logits(
            counts, params, module, model, dims, layers, record,
            jnp.asarray(sequence + [0] * (padded - len(sequence)),
                        jnp.int32), [len(sequence) - 1], len(sequence))
        if counts["requests_without_record"]:
            raise RuntimeError("the engine's record was not taken")
        gaps, _best = check._row_readings(
            logits, jnp.asarray([token], jnp.int32))
        return float(gaps[0])

    engine = serve.build_engine(module, model, params, mtp_modules=0)
    for request_id, prompt in prompts.items():
        engine.submit(Request(request_id, prompt, args.new_tokens))
    plain = {}
    while engine.pending():
        for request_id, tokens in engine.step():
            plain[request_id] = tokens
    engine.cache = None
    parted = []
    for request_id, prompt in prompts.items():
        with_module, without = done[request_id], plain[request_id]
        if with_module == without:
            continue
        at = next(i for i, pair in enumerate(zip(with_module, without))
                  if pair[0] != pair[1])
        sequence = prompt + with_module[:at]
        parted.append({
            "request": request_id, "at": at,
            "gap_with_module": gap(records[request_id], sequence,
                                   with_module[at]),
            "gap_without": gap(engine.take_decisions(request_id),
                               sequence, without[at])})
    return {"requests": len(prompts),
            "requests_equal": len(prompts) - len(parted),
            "tokens_equal_before_parting": sum(
                p["at"] for p in parted) + args.new_tokens * (
                    len(prompts) - len(parted)),
            "parted": parted,
            "parted_gap_max": max(
                [max(p["gap_with_module"], p["gap_without"])
                 for p in parted], default=0.0)}


if __name__ == "__main__":
    sys.exit(main())

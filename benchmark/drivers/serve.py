"""The serving cells: one engine on one chip behind the program's own
HTTP front end, loaded by the child process benchmark/loadgen.py.

Set-up (all of it counted in setup_s): weights from the seed in one
jitted call (bfloat16, as served), the engine through the program's
own entry (workloads/serve.build_engine -> ContinuousBatcher), a
throwaway request through every prefill bucket the cell's traffic can
reach (cold, and as a prefix-shared suffix) and through the decode
step, the front end, the load generator's start, and the traffic's
lead-in. Then the window; then, outside it, the reference check.

Nothing here names an architecture: the sizes, the parameter tree, the
program's model object and the reference come from the model module
the configuration file names (spec.load_model). And the engine is read
through its public surface alone (warmup_buckets, occupancy, pending,
active_request_ids, cancel, prefix_cache_clear, the attributes without
an underscore, and for a model that declares decisions the optional
take_decisions, found with getattr: benchmark/check.py has its shape),
so that a program PR that reshapes the engine's state does not have to
repair a file it may not edit."""

from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys
import time

from benchmark import (check, harness, spec, stats, tracered,
                       traffic_gen, weights)

LOADGEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "loadgen.py")
FAILED_MS = 1e9     # a tail that reaches a failed request reads this


@contextlib.contextmanager
def lean_cache_init():
    """A shim around engine construction, for set-up only.

    models/inference.init_cache builds an empty KV cache by running
    model.init EAGERLY, which also materialises a second full set of
    parameters and a second copy of the cache on the device before
    throwing them away: 7 + 7 + 2x the pool here, more than the chip
    holds. The same function under jax.jit yields the same zeros and
    the compiler drops the rest. Nothing of the timed path changes.
    PERF.md lists the program's own fix (Open questions) so that a
    later PR can delete this."""
    import jax
    from batch_shipyard_tpu.models import inference as inf
    original = inf.init_cache

    def jitted(model, params, batch_size):
        return jax.jit(lambda: original(model, None, batch_size))()

    inf.init_cache = jitted
    try:
        yield
    finally:
        inf.init_cache = original


def build_engine(model_module, model: dict, params, **overrides):
    """The engine as a user gets it: workloads/serve's parser and
    build_engine, with the configuration's sizes and the model object
    its model module makes of them. ``overrides`` are a control's
    keyword arguments for the module's program_model (the program's
    own lower-precision path switched on)."""
    from batch_shipyard_tpu.workloads import serve
    engine_cfg = model["engine"]
    config = model_module.program_model(
        model, model_module.dims(model), engine_cfg, **overrides)
    args = serve.build_parser().parse_args([
        "--num-slots", str(engine_cfg["num_slots"]),
        "--max-decode-len", str(engine_cfg["max_decode_len"]),
        "--kv-page-size", str(engine_cfg["kv_page_size"]),
        "--kv-num-pages", str(engine_cfg["kv_num_pages"]),
        "--temperature", "0", "--seed", "0"])
    with lean_cache_init():
        engine = serve.build_engine(args, config, params)
    if not (engine.paged and engine.prefix_cache) or engine.overcommit:
        raise RuntimeError("the engine is not the configuration's: "
                           "paged, prefix cache on, reservation")
    return engine


def reachable_buckets(engine, traffic: dict) -> tuple[list, list]:
    """(cold prompt lengths, shared-suffix lengths): one length in
    every prefill bucket this traffic can reach. Cold: the whole
    prompt. Shared: what is left after the shared prefix's whole
    pages, which is all a later request prefills."""
    prefix = int(traffic.get("shared_prefix_tokens", 0))
    low = traffic["prompt_tokens"]["min"]
    high = traffic["prompt_tokens"]["max"]

    buckets = engine.warmup_buckets()    # ascending, the last the cap

    def lengths(shortest: int, longest: int) -> list:
        out, n = [], shortest
        while True:
            bucket = next((b for b in buckets if b >= n), buckets[-1])
            out.append(min(bucket, longest))
            if bucket >= longest:
                return out
            n = bucket + 1

    cold = lengths(prefix + low, prefix + high)
    shared = []
    shared_pages = prefix // engine.page_size
    if engine.prefix_cache and shared_pages:
        skip = shared_pages * engine.page_size
        shared = lengths(prefix + low - skip, prefix + high - skip)
    return cold, shared


def warm_engine(ctx, engine, traffic: dict, vocab: int) -> dict:
    """Drive the engine directly, before the front end owns its
    thread: each reachable bucket once, decode steps with them."""
    from batch_shipyard_tpu.models.serving import Request
    import random
    rng = random.Random("warm-up")
    cold, shared = reachable_buckets(engine, traffic)
    prefix_len = int(traffic.get("shared_prefix_tokens", 0))
    prefix = [rng.randrange(1, vocab) for _ in range(prefix_len)]
    count = 0

    def drain(prompt):
        nonlocal count
        count += 1
        engine.submit(Request(request_id=f"warm-{count}",
                              prompt=prompt, max_new_tokens=3))
        while engine.pending():
            engine.step()

    def filler(n):
        return [rng.randrange(1, vocab) for _ in range(n)]

    for length in cold:
        engine.prefix_cache_clear()      # nothing to match: cold path
        drain(filler(length))
    if shared:
        engine.prefix_cache_clear()
        drain(prefix + filler(engine.page_size))   # publish the prefix
        skip = (prefix_len // engine.page_size) * engine.page_size
        for length in shared:
            drain(prefix + filler(skip + length - prefix_len))
    engine.prefix_cache_clear()
    return {"cold_buckets": cold, "shared_buckets": shared,
            "requests": count}


class StepRecorder:
    """The benchmark's span around engine.step (traced runs only):
    host clock, the slots and pages in use as the step starts, and a
    TraceAnnotation so that the profiler's idle gaps can be named."""

    def __init__(self, engine) -> None:
        import jax
        # (start, end, active slots, pages in use, queued, live tokens)
        self.steps: list = []
        inner = engine.step
        annotate = jax.profiler.TraceAnnotation

        def step():
            # the engine's own count: a shared prefix page counts
            # once, not once for every slot that reads it
            before = engine.occupancy()
            start = time.monotonic()
            with annotate("bench:engine.step"):
                out = inner()
            self.steps.append((
                start, time.monotonic(), before["slots_active"],
                before["kv_pages_in_use"], before["queued"],
                before["live_tokens"]))
            return out

        engine.step = step


def _start_loadgen(ctx, traffic_path: str, url: str, vocab: int):
    out = str(ctx.out_dir / "loadgen.json")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_"))}
    child = subprocess.Popen(
        [sys.executable, LOADGEN, "--traffic", traffic_path,
         "--seed", str(ctx.seed), "--seconds", str(ctx.seconds),
         "--vocab", str(vocab), "--url", url, "--out", out],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        env=env)
    ready = child.stdout.readline().strip()
    if ready != "READY":
        child.kill()
        child.wait()
        raise RuntimeError(f"load generator said {ready!r}")
    return child, out


def _request_rows(plan: dict, loaded: dict, mode: str) -> list:
    """One row per request the generator touched, joined with what it
    asked for, judged: ok only if every token it asked for arrived and
    the stream's tokens are the final line's."""
    by_idx = {r["idx"]: r for r in plan["requests"]}
    ws = loaded["window_start"]
    we = ws + loaded["window_s"]
    rows = []
    for record in loaded["records"]:
        asked = by_idx[record["idx"]]
        times = record["token_times"]
        final = record.get("final")
        ok = (final is not None and "error" not in record
              and final["tokens"] == record["tokens"]
              and len(record["tokens"]) == asked["max_new_tokens"])
        row = {"idx": record["idx"], "prompt": asked["prompt"],
               "tokens": record["tokens"], "ok": ok,
               "cut": bool(record.get("cut")),
               "error": record.get("error"),
               "token_times": times, "ended": record.get("ended"),
               "launched": record.get("launched"),
               "sent": record.get("sent"), "due": record.get("due"),
               "server_ttft_ms": (final or {}).get("ttft_ms")}
        if mode == "open":
            row["in_window"] = record.get("phase") == "window"
        else:
            row["in_window"] = (not row["cut"]
                                and ws <= (row["ended"] or 0) < we)
        rows.append(row)
    return rows


def _end_to_end(rows: list, mode: str, loaded: dict) -> dict:
    ws = loaded["window_start"]
    we = ws + loaded["window_s"]
    window = [r for r in rows if r["in_window"]]
    out = {"attempted": len(window),
           "failed": sum(1 for r in window if not r["ok"])}
    if mode == "open":
        ttft = [(r["token_times"][0] - r["due"]) * 1e3
                if r["ok"] else None for r in window]
        tpot = [((r["token_times"][-1] - r["token_times"][0]) * 1e3
                 / (len(r["token_times"]) - 1))
                if r["ok"] and len(r["token_times"]) > 1 else
                (None if not r["ok"] else 0.0) for r in window]
        out["ttft_p95_ms"] = stats.tail_percentile(ttft, 95, FAILED_MS)
        out["tpot_p95_ms"] = stats.tail_percentile(tpot, 95, FAILED_MS)
        out["ttft_p50_ms"] = stats.tail_percentile(ttft, 50, FAILED_MS)
        out["tpot_p50_ms"] = stats.tail_percentile(tpot, 50, FAILED_MS)
        out["generator_late_p95_ms"] = stats.percentile(
            [(r["launched"] - r["due"]) * 1e3 for r in window], 95)
    else:
        arrivals = [t for r in rows for t in r["token_times"]]
        out["serve_tokens_per_s"] = stats.rate_in_window(
            arrivals, ws, we)
    # Printed for the reader, not metrics: a far-off run (PERF.md,
    # section 7) says by these whether it had a stall, of what kind
    # and when: the longest wait between two tokens of one reply
    # inside the window, and the longest a request launched inside it
    # took to connect and send.
    gaps = [(after - before, before - ws) for r in rows
            for before, after in zip(r["token_times"],
                                     r["token_times"][1:])
            if ws <= before and after < we]
    connects = [(r["sent"] - r["launched"], r["launched"] - ws)
                for r in rows if r["sent"] is not None
                and ws <= r["launched"] < we]
    for name, pairs in (("token_gap", gaps), ("connect", connects)):
        longest, at = max(pairs, default=(0.0, 0.0))
        out[f"{name}_max_ms"] = longest * 1e3
        out[f"{name}_max_at_s"] = at
    return out


def _read_spans(path: str) -> list:
    spans = []
    if os.path.exists(path):
        with open(path, encoding="utf-8") as fh:
            for line in fh:
                if line.strip():
                    spans.append(json.loads(line))
    return spans


def _observations(ctx, rows, loaded, recorder, spans, engine, dims,
                  profile) -> dict:
    """What the per-layer readers read: named series (window only),
    counters, and the reduced device trace."""
    ws = loaded["window_start"]
    we = ws + loaded["window_s"]
    window = [r for r in rows if r["in_window"] and r["ok"]]
    window_ids = {f"bench-{r['idx']}" for r in window}
    # The first three and queue_wait_ms act on the first token, which
    # no cell reports yet (PERF.md, section 2): no committed metric
    # reads them. They stay so that a later PR can, with data files
    # alone; this driver may not be edited then.
    series = {
        "gen_late_ms": [(r["launched"] - r["due"]) * 1e3
                        for r in window if r.get("due") is not None],
        "client_ttft_ms": [(r["token_times"][0] - r["due"]) * 1e3
                           for r in window if r.get("due") is not None],
        "http_overhead_ms": [
            (r["token_times"][0] - r["sent"]) * 1e3
            - r["server_ttft_ms"] for r in window
            if r["server_ttft_ms"] is not None],
    }
    for kind, name in (("serve_queued", "queue_wait_ms"),
                       ("serve_prefill", "prefill_ms")):
        series[name] = [
            (s["end"] - s["start"]) * 1e3 for s in spans
            if s["kind"] == kind
            and s["attrs"].get("request_id") in window_ids]
    steps = [s for s in (recorder.steps if recorder else [])
             if ws <= s[0] < we]
    series["engine_step_ms"] = [(s[1] - s[0]) * 1e3 for s in steps]
    series["slots_active"] = [s[2] for s in steps]
    series["kv_pages_in_use"] = [s[3] for s in steps]
    counters = {
        "num_slots": engine.num_slots,
        "kv_pages_total": engine.occupancy()["kv_pages_total"],
        "window_requests_ok": len(window),
        "request_spans": len(series["queue_wait_ms"]),
    }
    obs = {"series": series, "counters": counters, "profile": profile,
           "dims": dims, "page_size": engine.page_size,
           "out_dir": ctx.out_dir,
           "peaks": ctx.peaks, "chips": len(ctx.devices)}
    if profile and recorder:
        # the engine steps that ran inside the traced slice, for the
        # decode kernel's bytes
        obs["traced_steps"] = [
            s for s in recorder.steps
            if profile["started"] <= s[0] and s[1] <= profile["stopped"]]
    return obs


def take_decisions(engine, layers: list, finished: dict,
                   record_control=None, seed: int = 0) -> None:
    """For a model that declares decision ``layers``: each finished
    request's record of the timed path's own choices, asked of the
    engine once, by the request's id ({id: row}), and kept on its row
    under "decisions". An engine without the method gives none, and
    the check then comes out not correct. ``record_control``
    ({"reroute_share": s}) is a control that corrupts what was handed
    over, before the check sees it."""
    if not layers:
        return
    take = getattr(engine, "take_decisions", None)
    share = float((record_control or {}).get("reroute_share", 0))
    for request_id, row in finished.items():
        record = take(request_id) if take else None
        if record is not None and share:
            record = check.reroute(record, layers, share,
                                   seed + row["idx"])
        row["decisions"] = record


class Session:
    """One engine, warmed for the cell's traffic. ``run`` uses it for
    one window; benchmark/calibrate.py for several (other seeds, other
    rates) without paying the set-up again."""

    def __init__(self, ctx, control=None, build=build_engine) -> None:
        """``control``: a control's overrides (a configuration's
        ``check.control``): keyword arguments of the module's
        program_model, and under "decisions" what is done to the
        engine's record before the check ({"reroute_share": s}).
        ``build`` makes the engine (a test hands its own)."""
        import jax
        import jax.numpy as jnp
        t0 = time.monotonic()
        self.ctx = ctx
        control = dict(control or {})
        self.record_control = control.pop("decisions", None)
        self.model = harness.merged(ctx.cell.config, ctx.tiny)
        self.traffic = harness.merged(ctx.cell.traffic, ctx.tiny)
        self.model_module = spec.load_model(self.model, ctx.root)
        self.dims = self.model_module.dims(self.model)
        self.decision_layers = spec.decision_layers(
            self.model_module, self.model, self.dims)
        self.leaves = self.model_module.param_leaves(self.dims)
        self.params = weights.make_params(self.leaves, ctx.seed,
                                          jnp.bfloat16)
        jax.block_until_ready(self.params)
        t_weights = time.monotonic()
        self.engine = build(self.model_module, self.model,
                            self.params, **control)
        warmed = warm_engine(ctx, self.engine, self.traffic,
                             self.dims["vocab"])
        ctx.note(f"set-up: weights {t_weights - t0:.2f}s, engine + "
                 f"warm-up {time.monotonic() - t_weights:.2f}s over "
                 f"{warmed}")
        self.recorder = None
        if ctx.trace:
            os.environ["SHIPYARD_TRACE_FILE"] = str(
                ctx.out_dir / "spans.jsonl")
            os.environ["SHIPYARD_TRACE_ID"] = "bench"
            os.environ["SHIPYARD_TRACE_SPAN_ID"] = "bench-run"
            self.recorder = StepRecorder(self.engine)

    def reseed(self, seed: int) -> None:
        """Other weights and traffic in the same engine (calibration
        only, between windows that have drained): same shapes, so
        nothing compiles."""
        import jax.numpy as jnp
        self.ctx.seed = seed
        self.engine.params = self.params = None
        self.params = weights.make_params(self.leaves, seed,
                                          jnp.bfloat16)
        self.engine.params = self.params
        for request_id in self.engine.active_request_ids():
            self.engine.cancel(request_id)
        if self.engine.pending():
            raise RuntimeError(
                f"reseed: {self.engine.pending()} requests are still "
                f"queued; the window before has not drained")
        self.engine.prefix_cache_clear()

    def window(self) -> dict:
        """Front end up, load generator through lead-in, window and
        drain, front end down. -> rows, values, peak, profile, obs."""
        from batch_shipyard_tpu.models.server import ServingFrontEnd
        ctx, engine, dims = self.ctx, self.engine, self.dims
        traffic_path = str(ctx.out_dir / "traffic.json")
        with open(traffic_path, "w", encoding="utf-8") as fh:
            json.dump(self.traffic, fh)
        if self.recorder is not None:
            self.recorder.steps.clear()
        front = ServingFrontEnd(engine, host="127.0.0.1",
                                port=0).start()
        child = None
        profiler = harness.ProfilerSlice(ctx) if ctx.trace else None
        try:
            child, out_path = _start_loadgen(
                ctx, traffic_path, front.url, dims["vocab"])
            plan = traffic_gen.generate(self.traffic, ctx.seed,
                                        ctx.seconds, dims["vocab"])
            entries_before = harness.cache_entries()
            t_go = time.monotonic() + 0.2
            child.stdin.write(f"GO {t_go!r}\n")
            child.stdin.flush()
            window_start = t_go + plan["lead_in_s"]
            if profiler is not None:
                slice_s = min(
                    float(self.traffic.get("trace_slice_s", 4.0)),
                    ctx.seconds / 2)
                begin = window_start + (ctx.seconds - slice_s) / 2
                profiler.run_between(begin, begin + slice_s)
            limit = (plan["lead_in_s"] + ctx.seconds + 60
                     + float(self.traffic.get("drain_limit_s", 30)))
            child.wait(timeout=max(1.0,
                                   t_go + limit - time.monotonic()))
            if child.returncode != 0:
                raise RuntimeError(
                    f"load generator exited {child.returncode}")
            peak = harness.memory_peak_bytes(ctx)
            compiled = harness.cache_entries() - entries_before
        finally:
            if child is not None and child.poll() is None:
                child.kill()
                child.wait()
            front.shutdown()
        if compiled:
            raise RuntimeError(
                f"{compiled} programs were compiled inside the "
                f"measured window: a shape was not warmed up")
        with open(out_path, encoding="utf-8") as fh:
            loaded = json.load(fh)
        rows = _request_rows(plan, loaded, plan["mode"])
        take_decisions(
            engine, self.decision_layers,
            {f"bench-{r['idx']}": r for r in rows
             if r["in_window"] and r["ok"]},
            self.record_control, ctx.seed)
        values = _end_to_end(rows, plan["mode"], loaded)
        values["setup_s"] = window_start - ctx.started
        ctx.note(f"window: {json.dumps(values)}; prefix cache "
                 f"{engine.prefix_stats()}; preemptions "
                 f"{engine.preemptions}; slo {engine.slo_stats()}")
        for row in [r for r in rows
                    if r["in_window"] and not r["ok"]][:5]:
            ctx.note(f"failed request {row['idx']}: {row['error']!r}"
                     f", {len(row['tokens'])} tokens")
        profile = obs = None
        if profiler is not None:
            profile = profiler.reduce()
            if profile is not None:
                profile["started"] = profiler.started
                profile["stopped"] = profiler.stopped
                with open(ctx.out_dir / "trace_described.txt", "w",
                          encoding="utf-8") as fh:
                    fh.write("\n".join(
                        tracered.describe(profile["trace"])))
            obs = _observations(
                ctx, rows, loaded, self.recorder,
                _read_spans(os.environ["SHIPYARD_TRACE_FILE"]),
                engine, dims, profile)
            obs["counters"]["memory_peak_bytes"] = peak
            if ctx.peaks:
                obs["counters"]["hbm_bytes"] = ctx.peaks["hbm_bytes"]
        return {"rows": rows, "values": values, "peak": peak,
                "profile": profile, "obs": obs}

    def check(self, rows: list) -> dict:
        """The reference over the requests the window finished: all of
        them, or as many as the configuration's
        ``check.served_tokens_at_most`` takes. -> numbers, and the
        readings they were made from."""
        finished = [r for r in rows if r["in_window"] and r["ok"]]
        section = self.model["check"]
        t_check = time.monotonic()
        readings = check.serve_gaps(
            self.params, self.model_module, self.model, self.dims,
            finished, self.decision_layers,
            section.get("served_tokens_at_most"), self.ctx.seed)
        numbers = check.gap_numbers(readings["gaps"],
                                    float(section["tail_from"]))
        if self.decision_layers:
            numbers.update(check.routing_numbers(
                readings, float(section["slack_from"])))
        return {"numbers": numbers,
                "requests": readings["requests"],
                "tokens": len(readings["gaps"]),
                "readings": readings,
                "seconds": time.monotonic() - t_check}


def run(ctx, build=build_engine) -> dict:
    session = Session(ctx, build=build)
    measured = session.window()
    # The reference check, outside the window. The pool is dropped
    # first so that the reference fits beside the weights.
    session.engine.cache = None
    session.engine = session.recorder = None
    checked = session.check(measured["rows"])
    with open(ctx.out_dir / "check_readings.json", "w",
              encoding="utf-8") as fh:
        json.dump(checked["readings"], fh)   # every token's, to look at
    numbers, readings = checked["numbers"], checked["readings"]
    limits = session.model["check"]["limits"]
    correct, lines = check.judge(numbers, limits)
    for line in lines:
        ctx.note(line)
    ctx.note(f"check: {checked['requests']} requests, "
             f"{checked['tokens']} served tokens against the float32 "
             f"reference in {checked['seconds']:.2f}s; without a limit: "
             f"gap_max {numbers['gap_max']!r}, "
             f"gap_mean {numbers['gap_mean']!r}")
    if readings["requests"] != readings["requests_finished"]:
        ctx.note(f"check: requests_checked {readings['requests']} of "
                 f"requests_finished {readings['requests_finished']} "
                 f"(check.served_tokens_at_most)")
    if session.decision_layers:
        ctx.note(f"check: the reference ran on the timed path's own "
                 f"choices in {len(session.decision_layers)} layers: "
                 f"{len(readings['slack'])} judged, positions_unrecorded "
                 f"{readings['positions_unrecorded']} of "
                 f"{readings['positions']}; without a limit: "
                 f"routing_flip_share {numbers['routing_flip_share']!r}"
                 f", slack_max {numbers['slack_max']!r}")
        if readings["requests_without_record"]:
            ctx.note(f"check: NOT CORRECT: the model declares "
                     f"decisions and the engine gave no record of them "
                     f"(take_decisions) for "
                     f"{readings['requests_without_record']} finished "
                     f"requests")
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

"""The roofline share of ONE PART of a step program over the traced
slice, timed by the SPAN its operations cover inside each launch:
params {"kernel": module under kernels/, "program_pattern": regular
expression over the names of the device trace's program events,
"event_pattern": regular expression over the names of its operation
events}.

WHY A SPAN. ops_in_program_roofline sums the operation events whose
name holds one of the part's arguments (a weight, a cache leaf: the
only thing in an event's HLO text that says which layer it belongs
to). That finds a part's time only where every operation that moves
its bytes reads an ARGUMENT. The compiler is free not to: it prefetches
a weight in slices (``%slice-start.66 = ... slice-start(
%params__mtp____layer_0____attn____v_proj____kernel__.1)``, a few
microseconds to issue) and hands the matmul ``%slice-done.66``; it
hands a paged-attention kernel the fusion that wrote this step's rows
into the pool (``%fusion.21``) and not the pool's argument. Those
matmuls and that kernel then carry no argument's name, their time is
left out, and a share of the part's WHOLE bytes over the named
operations' time reads above 100 (K-EXAONE's module: 130, PERF.md
PR 42).

A part whose operations are scheduled together (a module behind the
stack: everything it computes depends on the stack's last result, and
what follows it depends on its own) is timed whole by the first and
the last of its named operations: this reader takes, in every launch
of a matching program, the span from the earliest start to the latest
end of the matching operation events that start inside the launch
(containers left out), sums the spans, and holds them against
kernels/<kernel>.work(obs, {"program": launches with a span}). What
lies inside the span and is not the part's (an operation of another
part scheduled between them, a prefetch of the part's weights issued
early, the wait of one issued late) is counted as the part's time: the
share errs LOW, never high. Nothing is clamped. No profile, no program
line, no matching launch or event, or no work, reads None."""

import bisect
import re

from benchmark import spec, tracered

PROGRAM_LINE = "XLA Modules"


def span_seconds(trace: dict, program_pattern: str,
                 event_pattern: str) -> tuple[float, int]:
    """(seconds of the spans the matching operations cover inside
    matching launches, launches with such a span), averaged over the
    devices that ran any."""
    program, event = re.compile(program_pattern), re.compile(
        event_pattern)
    seconds, calls, devices = 0.0, 0, 0
    for plane in trace["planes"]:
        if not tracered.DEVICE_PLANE.match(plane["name"]):
            continue
        launches = sorted(
            (e[1], e[1] + e[2]) for line in plane["lines"]
            if line["name"] == PROGRAM_LINE
            for e in line["events"] if program.search(e[0]))
        if not launches:
            continue
        starts = [start for start, _end in launches]
        spans = {}                    # launch -> [first start, last end]
        for line in plane["lines"]:
            if line["name"] not in tracered.OP_LINES:
                continue
            for name, start, duration in line["events"]:
                at = bisect.bisect_right(starts, start) - 1
                if at < 0 or start >= launches[at][1] \
                        or not event.search(name) \
                        or tracered.CONTAINER.match(
                            tracered.short_name(name)):
                    continue
                span = spans.setdefault(at, [start, start + duration])
                span[0] = min(span[0], start)
                span[1] = max(span[1], start + duration)
        if not spans:
            continue
        devices += 1
        calls += len(spans)
        seconds += sum(end - start for start, end in spans.values()) / 1e9
    if not devices:
        return 0.0, 0
    return seconds / devices, calls // devices


def read(obs, params):
    profile = obs.get("profile")
    peaks = obs.get("peaks")
    if not profile or not profile.get("trace") or not peaks:
        return None
    seconds, calls = span_seconds(
        profile["trace"], params["program_pattern"],
        params["event_pattern"])
    if not seconds or not calls:
        return None
    work = spec.load_module(
        spec.ROOT, spec.load_benchmark(),
        f"kernels/{params['kernel']}.py").work(obs, {"program": calls})
    if work is None:
        return None
    by_flops = work["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    obs.setdefault("roofline_bound", {})[params["kernel"]] = (
        "compute" if by_flops > by_bytes else "memory")
    return 100.0 * max(by_flops, by_bytes) / seconds

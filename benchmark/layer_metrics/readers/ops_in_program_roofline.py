"""The roofline share of SOME operations of ONE step program over the
traced slice: params {"kernel": module under kernels/,
"program_pattern": regular expression over the names of the device
trace's program events, "event_pattern": regular expression over the
names of its operation events}.

WHAT A NAME HOLDS. A device trace's operation event is named by the
instruction's HLO text (``%fusion.42 = bf16[96,40,1280]... fusion(
bf16[40,4096,1280]... %params__layer_1____experts____experts_up__.1,
...)``), which does NOT carry the ``layer_<i>/<kind>/`` scope of the
lowered program's metadata: it names a layer only through the
OPERANDS that are arguments of the program, a weight
(``%params__layer_<i>____<kind>____<leaf>__``) or a cache leaf
(``%cache__layer_<i>____<kind>____<leaf>__``). So a block kind's
operations are found by the arguments they read: every matmul reads
its weight, a state's read-modify-write reads its cache leaf; what is
NOT found are the elementwise operations between them, on activations
alone (a few microseconds each at a decode step's row counts), so the
time is short of the kind's whole by that little and the share high
by as much.

kernel_roofline takes every operation event whose name matches,
whatever program launched it, and a model's prefill and decode
programs read the same arguments. This reader keeps
the matching operation events that START inside a launch of a matching
program (the device planes' "XLA Modules" line, one event a launch),
sums their time, and holds it against the least the chip could take
for that part of every such launch: kernels/<kernel>.work(obs,
{"program": launches}). Operations that merely contain others (a
while loop) are left out. Nothing is clamped: a share above 100 %
means the work was counted too high or the events do not hold all the
time. No profile, no program line, no matching launch or event, or no
work, reads None."""

import bisect
import re

from benchmark import spec, tracered

PROGRAM_LINE = "XLA Modules"


def seconds_inside(trace: dict, program_pattern: str,
                   event_pattern: str) -> tuple[float, int]:
    """(seconds of the matching operations inside matching launches,
    launches), averaged over the devices that ran any."""
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
        devices += 1
        calls += len(launches)
        for line in plane["lines"]:
            if line["name"] not in tracered.OP_LINES:
                continue
            for name, start, duration in line["events"]:
                at = bisect.bisect_right(starts, start) - 1
                if at >= 0 and start < launches[at][1] \
                        and event.search(name) \
                        and not tracered.CONTAINER.match(
                            tracered.short_name(name)):
                    seconds += duration / 1e9
    if not devices:
        return 0.0, 0
    return seconds / devices, calls // devices


def read(obs, params):
    profile = obs.get("profile")
    peaks = obs.get("peaks")
    if not profile or not profile.get("trace") or not peaks:
        return None
    seconds, calls = seconds_inside(
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

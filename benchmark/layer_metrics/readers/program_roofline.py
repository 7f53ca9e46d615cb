"""A whole step PROGRAM's share of its roofline over the traced slice:
params {"kernel": module under kernels/, "program_pattern": regular
expression over the names of the device trace's program events}.

Where kernel_roofline times one kernel by its operation events, this
times a compiled program by the events of the device planes'
"XLA Modules" line (one event a launch, from the program's first
operation to its last), and holds that time against the least the
chip could take for what every launch HAS to do: the larger of
required operations over peak operations/s and required bytes over
peak bytes/s, both from kernels/<kernel>.work(obs, calls). Nothing is
clamped: a share above 100 % means the work was counted too high. No
profile, no such line, no matching event or no work reads None."""

import re

from benchmark import spec, tracered

PROGRAM_LINE = "XLA Modules"


def program_seconds(trace: dict, pattern: str) -> tuple[float, int]:
    """(seconds, launches) of the matching programs, averaged over the
    devices that ran any."""
    rx = re.compile(pattern)
    seconds, calls, devices = 0.0, 0, 0
    for plane in trace["planes"]:
        if not tracered.DEVICE_PLANE.match(plane["name"]):
            continue
        hits = [e for line in plane["lines"]
                if line["name"] == PROGRAM_LINE
                for e in line["events"] if rx.search(e[0])]
        if hits:
            devices += 1
            seconds += sum(e[2] for e in hits) / 1e9
            calls += len(hits)
    if not devices:
        return 0.0, 0
    return seconds / devices, calls // devices


def read(obs, params):
    profile = obs.get("profile")
    peaks = obs.get("peaks")
    if not profile or not profile.get("trace") or not peaks:
        return None
    seconds, calls = program_seconds(profile["trace"],
                                     params["program_pattern"])
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

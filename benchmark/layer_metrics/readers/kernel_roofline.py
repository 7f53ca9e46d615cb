"""A kernel's share of its roofline over the traced slice:
params {"kernel": module under kernels/, "event_patterns": {kind:
regular expression over the device trace's event names}}.

The least time the chip could take for the calls seen (the larger of
required operations over peak operations/s and required bytes over
peak bytes/s, both from kernels/<kernel>.work) over the time the
kernel's events took. ``obs["roofline_bound"]`` records which of the
two bound it. Nothing is clamped: a share above 100 % means the work
was counted too high or the events do not hold all the time."""

from benchmark import spec, tracered


def read(obs, params):
    profile = obs.get("profile")
    peaks = obs.get("peaks")
    if not profile or not profile.get("events") or not peaks:
        return None
    seconds, calls = 0.0, {}
    for kind, pattern in params["event_patterns"].items():
        kind_seconds, kind_calls = tracered.kernel_seconds(
            profile["events"], pattern)
        seconds += kind_seconds
        calls[kind] = kind_calls
    if not seconds or not any(calls.values()):
        return None
    work = spec.load_module(
        spec.ROOT, spec.load_benchmark(),
        f"kernels/{params['kernel']}.py").work(obs, calls)
    if work is None:
        return None
    by_flops = work["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    obs.setdefault("roofline_bound", {})[params["kernel"]] = (
        "compute" if by_flops > by_bytes else "memory")
    return 100.0 * max(by_flops, by_bytes) / seconds

"""A kernel's device time as a share of the step programs that call
it, over the traced slice: params {"event_pattern": regular expression
over the device trace's operation events, "program_pattern": regular
expression over the names of its program events}.

The sum of the matching operation events' durations over the sum of
the matching programs' launches (the device planes' "XLA Modules"
line, one event a launch), in per cent: what part of a step the kernel
IS. Both sums are over the same slice and the same devices; the
kernel's events lie inside those launches where the pattern names a
kernel only that program calls. No profile, no matching event or no
matching launch reads None."""

from benchmark import spec, tracered


def read(obs, params):
    profile = obs.get("profile")
    if not profile or not profile.get("events") \
            or not profile.get("trace"):
        return None
    kernel, calls = tracered.kernel_seconds(profile["events"],
                                            params["event_pattern"])
    program, launches = spec.load_module(
        spec.ROOT, spec.load_benchmark(),
        "layer_metrics/readers/program_roofline.py").program_seconds(
            profile["trace"], params["program_pattern"])
    if not calls or not launches or not program:
        return None
    return 100.0 * kernel / program

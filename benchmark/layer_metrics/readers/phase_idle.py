"""Device-idle seconds put down to the engine's own phases, as a
percentage of the traced slice: params {"phases": [phase, ...]}.

The engine opens a ``serve:<phase>`` TraceAnnotation around each leaf
phase of a step (admit, prefill, slot_update, grow_pages, dispatch,
readback, emit), so the phases lie on the device trace's clock. Every
gap between the first device's operations goes to the phase that
covers its middle (tracered.idle_gaps), or to ``uncovered`` where
none does (between two steps, between two phases). The metric is the
gaps of ITS phases over the slice's seconds; two metrics that split
the phases between them add up to the cell's idle share less
``uncovered`` and the slice's two ends.

The annotations are read from the xplane file the traced run leaves
under ``profile/`` of its own output directory, which the driver names
in ``obs["out_dir"]``: the driver's own reduction keeps only ``bench:``
host events. No directory named, no file, no ``serve:`` annotation (a
program without them) or no device plane (the CPU rehearsal) reads
None."""

import os

from benchmark import tracered

PREFIX = "serve:"
UNCOVERED = "uncovered"


def split(trace: dict):
    """{phase or UNCOVERED: idle seconds}, or None."""
    events = tracered.device_op_events(trace)
    spans = tracered.host_spans(trace, PREFIX)
    if not events or not spans:
        return None
    return dict(tracered.idle_gaps(events, spans, n=1000,
                                   between=UNCOVERED))


def value(gaps, slice_s: float, params: dict):
    if not gaps or not slice_s:
        return None
    return 100.0 * sum(gaps.get(name, 0.0)
                       for name in params["phases"]) / slice_s


def read(obs, params):
    profile = obs.get("profile")
    if not profile:
        return None
    if "phase_idle" not in obs:
        out_dir = obs.get("out_dir")
        path = out_dir and tracered.newest_xplane(
            os.path.join(str(out_dir), "profile"))
        obs["phase_idle"] = path and split(
            tracered.from_xplane(path, keep_host_prefix=PREFIX))
        if obs["phase_idle"]:
            ranked = sorted(obs["phase_idle"].items(),
                            key=lambda kv: -kv[1])
            print(f"device idle by engine phase, of a "
                  f"{profile['window_s']:.3f} s slice with "
                  f"{profile['window_s'] - profile['busy_s']:.3f} s "
                  f"idle: " + ", ".join(
                      f"{name} {seconds:.4f} s"
                      for name, seconds in ranked), flush=True)
    return value(obs["phase_idle"], profile["window_s"], params)

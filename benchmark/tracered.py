"""Reduction from a profiler trace to numbers: device busy time (the
union of the intervals in which an operation ran), idle gaps named by
what the host was doing, time by operation, and a kernel's time by
the name its events carry.

Two stages. ``from_xplane`` turns the profiler's .xplane.pb into a
plain structure (needs jax, nothing else):

    {"planes": [{"name": str,
                 "lines": [{"name": str,
                            "events": [[name, start_ns, dur_ns], ...]
                           }]}]}

Everything after that is pure Python over that structure and is tested
on a small recorded trace (benchmark/testdata/). Times are
nanoseconds on the trace's one clock."""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# Lines of a device plane that hold one event per executed operation.
OP_LINES = ("XLA Ops",)
# Operations whose event only spans the events of their body.
CONTAINER = re.compile(r"^(while|conditional|call)([.\d]|$)")
HOST_SPAN_PREFIX = "bench:"
# The annotation (behind HOST_SPAN_PREFIX) that the harness holds open
# from its slice's start stamp to its stop stamp: the host's slice on
# the trace's clock.
SLICE_MARK = "traced_slice"


def short_name(name: str) -> str:
    """An event's operation, short and stable: the part before the
    HLO text's `` = ``, without the leading ``%`` and the trailing
    instance number, so that the sixteen layers' calls of one
    operation add up under one name. ``custom-call`` is kept as a
    mark where the operation is one (a Pallas kernel)."""
    head, sep, rest = name.partition(" = ")
    head = re.sub(r"(\.\d+)+$", "", head.lstrip("%"))
    if sep and "custom-call(" in rest:
        head += " custom-call"
    return head[:120]


def newest_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def from_xplane(path: str, keep_host_prefix: str = HOST_SPAN_PREFIX
                ) -> dict:
    """Device planes whole; of host planes only the events whose name
    starts with ``keep_host_prefix`` (the benchmark's own
    TraceAnnotations), so the structure stays small."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(keep_host_prefix)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def describe(trace: dict, top: int = 30) -> list[str]:
    """A by-hand look: planes, lines, and the names that take most
    time on each line."""
    out = []
    for plane in trace["planes"]:
        out.append(f"plane {plane['name']}")
        for line in plane["lines"]:
            totals: dict = {}
            calls: dict = {}
            for name, _start, dur in line["events"]:
                name = short_name(name)
                totals[name] = totals.get(name, 0) + dur
                calls[name] = calls.get(name, 0) + 1
            out.append(f"  line {line['name']!r}: "
                       f"{len(line['events'])} events, "
                       f"{sum(totals.values()) / 1e6:.3f} ms summed")
            for name, dur in sorted(totals.items(),
                                    key=lambda kv: -kv[1])[:top]:
                out.append(f"    {dur / 1e6:10.3f} ms  {calls[name]:6d}x"
                           f"  {name}")
    return out


def device_op_events(trace: dict) -> dict[int, list]:
    """{device index: [[name, start, dur], ...]} from each device
    plane's operation line(s)."""
    out: dict[int, list] = {}
    for plane in trace["planes"]:
        match = DEVICE_PLANE.match(plane["name"])
        if not match:
            continue
        events = [e for line in plane["lines"]
                  if line["name"] in OP_LINES for e in line["events"]]
        if events:
            out[int(match.group(1))] = sorted(events,
                                              key=lambda e: e[1])
    return out


def host_spans(trace: dict, prefix: str = HOST_SPAN_PREFIX) -> list:
    """[[label, start, end], ...] of the benchmark's annotations on
    any host line, label without the prefix."""
    spans = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(prefix):
                    spans.append([name[len(prefix):], start,
                                  start + dur])
    return sorted(spans, key=lambda s: s[1])


def merge(intervals: Iterable) -> list[list[int]]:
    """Union of [start, end) intervals as a sorted disjoint list."""
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def total(intervals: Iterable) -> int:
    return sum(end - start for start, end in intervals)


def subtract(base: list, cover: list) -> list[list[int]]:
    """The parts of merged ``base`` not covered by merged ``cover``."""
    out = []
    j = 0
    for start, end in base:
        cursor = start
        while j < len(cover) and cover[j][1] <= cursor:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < end:
            if cover[k][0] > cursor:
                out.append([cursor, cover[k][0]])
            cursor = max(cursor, cover[k][1])
            k += 1
        if cursor < end:
            out.append([cursor, end])
    return out


def busy_seconds(events_by_device: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    if not events_by_device:
        return 0.0
    per_device = [
        total(merge([e[1], e[1] + e[2]] for e in events))
        for events in events_by_device.values()]
    return sum(per_device) / len(per_device) / 1e9


def span_edges(events_by_device: dict) -> Optional[tuple[int, int]]:
    """(earliest event's start, latest event's end) over the devices,
    or None for no events."""
    edges = [(e[1], e[1] + e[2])
             for events in events_by_device.values() for e in events]
    if not edges:
        return None
    return min(start for start, _ in edges), max(end for _, end in edges)


def span_seconds(events_by_device: dict) -> float:
    """Seconds from the earliest event's start to the latest event's
    end over the devices: the least the traced interval can have
    lasted, and no device's busy time can exceed it."""
    edges = span_edges(events_by_device)
    return (edges[1] - edges[0]) / 1e9 if edges else 0.0


def reduce_slice(trace: dict, host_slice_s: float) -> dict:
    """A traced run's account of its profiler slice: the trace, and
    the seconds the host held the slice open (both ends stamped with
    tracing on). The traced interval contains the host's slice AND
    every device event (the device is traced until stop_trace takes
    hold, and the two clocks skew by milliseconds), so ``window_s``
    is the longer of the slice and the events' span, and ``busy_s``,
    a union inside that span, cannot exceed it. Nothing is clipped,
    subtracted or clamped: a trace whose busy time passes its own
    span is wrong for another reason and has to show.

    ``edges_s`` is [first event's start after the slice mark's start,
    last event's end after the mark's end] on the trace's clock, None
    without a mark or without events. The mark names no idle gap."""
    events = device_op_events(trace)
    spans = host_spans(trace)
    mark = next((s for s in spans if s[0] == SLICE_MARK), None)
    edges = span_edges(events)
    span_s = span_seconds(events)
    return {
        "trace": trace, "events": events,
        "busy_s": busy_seconds(events),
        "window_s": max(host_slice_s, span_s),
        "host_slice_s": host_slice_s, "span_s": span_s,
        "edges_s": [(edges[0] - mark[1]) / 1e9,
                    (edges[1] - mark[2]) / 1e9]
        if mark and edges else None,
        "breakdown": {
            "device_ops": top_ops(events),
            "idle_gaps": idle_gaps(
                events, [s for s in spans if s[0] != SLICE_MARK])}}


def describe_slice(profile: dict) -> str:
    """One line of a traced run's account: the slice's edges, so that
    a far-off idle share can be read off the run."""
    busy, span = profile["busy_s"], profile["span_s"]
    edges = profile["edges_s"]
    return (
        f"traced slice: host {profile['host_slice_s']:.9f} s, device "
        f"events' span {span:.9f} s, window_s "
        f"{profile['window_s']:.9f} s (the longer), busy {busy:.9f} s, "
        f"gaps inside the span {(span - busy) * 1e3:.6f} ms, window_s "
        f"- busy {(profile['window_s'] - busy) * 1e3:.6f} ms; "
        + (f"first event {edges[0] * 1e3:+.3f} ms after the slice's "
           f"start, last event's end {edges[1] * 1e3:+.3f} ms after "
           f"its end" if edges else "no slice mark or no device event"))


def top_ops(events_by_device: dict, n: int = 10) -> list:
    """[[name, seconds], ...]: operations by summed duration, averaged
    over the devices. Operations that merely CONTAIN others (a while
    loop, a call) are left out: their time is their children's."""
    totals: dict = {}
    for events in events_by_device.values():
        for name, _start, dur in events:
            name = short_name(name)
            if CONTAINER.match(name):
                continue
            totals[name] = totals.get(name, 0) + dur
    count = max(1, len(events_by_device))
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[name, dur / count / 1e9] for name, dur in ranked]


def kernel_seconds(events_by_device: dict, pattern: str
                   ) -> tuple[float, int]:
    """(seconds, calls) of the events whose name matches ``pattern``
    (a regular expression), summed over one device's line and
    averaged over the devices."""
    rx = re.compile(pattern)
    seconds, calls = 0.0, 0
    for events in events_by_device.values():
        hits = [e for e in events if rx.search(e[0])]
        seconds += sum(e[2] for e in hits) / 1e9
        calls += len(hits)
    count = max(1, len(events_by_device))
    return seconds / count, calls // count


def idle_gaps(events_by_device: dict, spans: list, n: int = 10,
              between: str = "between spans") -> list:
    """[[label, seconds], ...]: idle time of the first device by what
    the host was doing, the label being the benchmark span that covers
    the middle of each gap (``between`` where none does)."""
    if not events_by_device:
        return []
    events = events_by_device[min(events_by_device)]
    busy = merge([e[1], e[1] + e[2]] for e in events)
    gaps = subtract([[busy[0][0], busy[-1][1]]], busy)
    totals: dict = {}
    for start, end in gaps:
        middle = (start + end) // 2
        label = between
        for name, s_start, s_end in spans:
            if s_start <= middle < s_end:
                label = name
                break
        totals[label] = totals.get(label, 0) + (end - start)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [[label, dur / 1e9] for label, dur in ranked]

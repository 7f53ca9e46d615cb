#!/usr/bin/env python3
"""A traced benchmark run's device time by operation AND result shape,
for the operations that move memory: the benchmark's ``breakdown``
names operations only, and ``copy`` is both a copy of a 101 MB pool
leaf and XLA staging a weight matrix into VMEM (PERF.md, section 5,
PR 25). With a program pattern: EVERY operation that starts inside a
launch of a program whose name matches (``jit__prefill_paged``), by
time, and the launches' own time: what one kind of launch is made of
(PERF.md, PR 37: no copy of an expert stack in a grouped prefill). Not
part of the benchmark; reads what a ``--trace 1`` run left under
<checkout>/.bench_out/profile.

    python3 tools/trace_ops_by_shape.py <checkout> [program-pattern]"""
import bisect
import collections
import re
import sys

sys.path.insert(0, sys.argv[1])
from benchmark import tracered  # noqa: E402

program = re.compile(sys.argv[2]) if len(sys.argv) > 2 else None
path = tracered.newest_xplane(sys.argv[1] + "/.bench_out/profile")
trace = tracered.from_xplane(path)
launches = sorted(
    (e[1], e[1] + e[2]) for plane in trace["planes"]
    if tracered.DEVICE_PLANE.match(plane["name"])
    for line in plane["lines"] if line["name"] == "XLA Modules"
    for e in line["events"] if program and program.search(e[0]))
starts = [start for start, _end in launches]
if program:
    print(f"{len(launches)} launches of /{program.pattern}/, "
          f"{sum(b - a for a, b in launches) / 1e9:.4f} s")
totals = collections.defaultdict(lambda: [0, 0])
for _dev, events in tracered.device_op_events(trace).items():
    for name, start, dur in events:
        short = tracered.short_name(name)
        if program:
            at = bisect.bisect_right(starts, start) - 1
            if at < 0 or start >= launches[at][1] \
                    or tracered.CONTAINER.match(short):
                continue
        elif not re.match(
                r"(copy|reshape|slice|transpose|bitcast|attn)", short):
            continue
        shape = re.search(r" = \(?([a-z0-9]+\[[\d,]*\])(\{[^}]*\})?", name)
        key = (short, shape.group(1) if shape else "?",
               "S(1)" if shape and shape.group(2) and "S(1)" in shape.group(2)
               else "")
        totals[key][0] += dur
        totals[key][1] += 1
for key, (dur, n) in sorted(totals.items(), key=lambda kv: -kv[1][0])[:25]:
    print(f"  {dur / 1e9:8.4f} s {n:6d}x  {key[0]:40s} {key[1]} {key[2]}")

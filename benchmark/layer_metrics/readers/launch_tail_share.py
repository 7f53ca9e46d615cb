"""The share of a step program's launches that lies BEHIND one of its
operations, over the traced slice: params {"program_pattern": regular
expression over the names of the device trace's program events,
"event_pattern": regular expression over the names of its operation
events}.

What a block pass does after the stack (the head over every position
of every block, the float32 cast, each position's best token, the
logsumexp of its confidence, the unmask choice, the cursors' rewind)
reads one argument of the program, the head's weight, in its first
and by far longest operation; the operations behind it read
activations alone and carry no argument's name (layer_metrics/readers/
ops_in_program_roofline.py has what a name holds). Everything behind
the head depends on it and nothing before it does, so that part of a
launch is timed whole from the START of the LONGEST matching operation
event inside the launch (a prefetch of the same argument issued early
is a few microseconds long and is not taken for it) to the launch's
END; the share is the sum of those tails over the sum of the launches
that have one, in per cent. Containers are left out. No profile, no
program line, no matching launch or event reads None."""

import bisect
import re

from benchmark import tracered

PROGRAM_LINE = "XLA Modules"


def tail_share(trace: dict, program_pattern: str, event_pattern: str):
    program, event = re.compile(program_pattern), re.compile(
        event_pattern)
    tails = wholes = 0.0
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
        longest = {}            # launch -> (duration, start)
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
                longest[at] = max(longest.get(at, (0, 0)),
                                  (duration, start))
        for at, (_duration, start) in longest.items():
            tails += launches[at][1] - start
            wholes += launches[at][1] - launches[at][0]
    return 100.0 * tails / wholes if wholes else None


def read(obs, params):
    profile = obs.get("profile")
    if not profile or not profile.get("trace"):
        return None
    return tail_share(profile["trace"], params["program_pattern"],
                      params["event_pattern"])

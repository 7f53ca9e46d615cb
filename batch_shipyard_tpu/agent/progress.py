"""Task progress beats: the liveness contract behind the wedge watchdog.

The failure mode that motivates this is a process that stays ALIVE but
makes no progress forever — a device-runtime call that blocks, a
collective stuck on a dead ICI peer. Wall-time
limits catch runaways, heartbeats catch dead nodes; neither catches a
wedged-but-breathing task. Progress beats do: the agent exports
$SHIPYARD_PROGRESS_FILE into every task env, instrumented workloads
touch it on every unit of progress (the train-step wrappers in
parallel/train.py beat on every step call), and the task runner's
watchdog kills any task whose spec declares `progress_deadline_seconds`
once the file goes stale past that deadline — converting an unbounded
hang into a bounded retry through the retry supervisor.

Beats are throttled (at most one mtime write per BEAT_INTERVAL) so a
microsecond step loop never turns the liveness file into an I/O hot
path. With no sink configured the recorder is a no-op: workloads run
unchanged outside pools, exactly like the goodput recorder.
"""

from __future__ import annotations

import json
import os
import time
from typing import Optional

# Env var the agent exports into every task: the liveness file the
# watchdog stats. Process spawn counts as the first beat (the runner
# seeds the file), so un-instrumented tasks only ever trip the
# watchdog if they opt in via progress_deadline_seconds AND stall.
PROGRESS_FILE_ENV = "SHIPYARD_PROGRESS_FILE"

# The task's own watchdog deadline, exported alongside the file so the
# throttle can scale itself: a fixed 1s throttle against a ~1s deadline
# would drop the very beats that prove liveness, and the watchdog would
# kill a task that is progressing every step.
PROGRESS_DEADLINE_ENV = "SHIPYARD_PROGRESS_DEADLINE"

# Throttle ceiling: minimum seconds between mtime writes from beat()
# when no (or a generous) deadline is exported.
BEAT_INTERVAL = 1.0

_last_beat_at = 0.0


def _throttle_seconds() -> float:
    """Beats must land well inside the watchdog deadline: throttle at
    a quarter of the exported deadline, capped at BEAT_INTERVAL."""
    raw = os.environ.get(PROGRESS_DEADLINE_ENV)
    if raw:
        try:
            return min(BEAT_INTERVAL, max(0.01, float(raw) / 4.0))
        except ValueError:
            pass
    return BEAT_INTERVAL


def progress_path() -> Optional[str]:
    """The liveness file for THIS process, or None (beats disabled)."""
    return os.environ.get(PROGRESS_FILE_ENV) or None


def beat() -> None:
    """Record one unit of progress: bump the liveness file's mtime —
    the only signal the watchdog reads. No-op when unset; never
    raises — a liveness write must not fail the work it measures."""
    global _last_beat_at
    path = progress_path()
    if path is None:
        return
    now = time.monotonic()
    if now - _last_beat_at < _throttle_seconds():
        return
    _last_beat_at = now
    try:
        os.utime(path, None)
    except OSError:
        # First beat before the runner's seed (or the file was
        # removed underneath us): create it.
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            with open(path, "w", encoding="utf-8"):
                pass
        except OSError:
            pass


def seed(path: str) -> None:
    """Write the initial beat (process spawn) so the watchdog's clock
    starts at launch, not at epoch 0."""
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w", encoding="utf-8"):
            pass
    except OSError:
        pass


def last_beat(path: str) -> Optional[float]:
    """Wall-clock time of the task's most recent beat (file mtime), or
    None when the file does not exist."""
    try:
        return os.stat(path).st_mtime
    except OSError:
        return None


# --------------------------- sched hints ---------------------------
#
# The liveness beat proves a task is MOVING; scheduling hints say what
# it would COST to stop it. Instrumented workloads publish
# {step, ckpt_step, step_seconds, cache_identity} to the hints file
# the agent exports ($SHIPYARD_SCHED_HINTS_FILE); the agent mirrors it
# into the task row's sched_hints column on each heartbeat, where the
# preemption sweep's shared victim-cost policy
# (sched/policy.py victim_cost_from_row) prices replay rework from it.
# Purely advisory, same contract as beats: no sink → no-op, a failed
# write never fails the work it describes.

SCHED_HINTS_FILE_ENV = "SHIPYARD_SCHED_HINTS_FILE"


def sched_hints_path() -> Optional[str]:
    """The hints file for THIS process, or None (hints disabled)."""
    return os.environ.get(SCHED_HINTS_FILE_ENV) or None


def record_sched_hints(step: Optional[int] = None,
                       ckpt_step: Optional[int] = None,
                       step_seconds: Optional[float] = None,
                       cache_identity: Optional[str] = None) -> None:
    """Publish this task's preemption-cost inputs (atomic
    tmp+rename, so the agent's heartbeat read never sees a torn
    write). Fields left None are omitted — callers report what they
    know (a checkpointer knows ckpt_step, a step loop knows
    step/step_seconds)."""
    path = sched_hints_path()
    if path is None:
        return
    hints: dict = {}
    if step is not None:
        hints["step"] = int(step)
    if ckpt_step is not None:
        hints["ckpt_step"] = int(ckpt_step)
    if step_seconds is not None:
        hints["step_seconds"] = float(step_seconds)
    if cache_identity:
        hints["cache_identity"] = str(cache_identity)
    if not hints:
        return
    try:
        prior = read_sched_hints(path) or {}
        prior.update(hints)
        tmp = f"{path}.tmp.{os.getpid()}"
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(prior, fh, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass


def read_sched_hints(path: str) -> Optional[dict]:
    """The hints dict at ``path``, or None (absent/corrupt — a torn
    or junk file is advisory data lost, never an agent crash)."""
    try:
        with open(path, encoding="utf-8") as fh:
            data = json.load(fh)
        return data if isinstance(data, dict) else None
    except (OSError, ValueError):
        return None

"""Arrival traces: the synthetic workloads the simulator schedules.

Every generator is a pure function of its arguments (seeded
``random.Random`` instances, never the global RNG, never the wall
clock) so the same (seed, params) always yields the same trace —
half of the byte-identical determinism contract.

Shapes:

* ``poisson_trace``          — steady Poisson arrivals, mixed
                               identities/checkpoint cadences.
* ``diurnal_trace``          — sinusoidal day/night rate profile (the
                               autoscale provisioning-vs-queueing
                               trade only exists under load swings).
* ``scheduler_scale_trace``  — the scheduler scale drill's shape: one
                               bulk submission of up to 10^6 tiny
                               tasks at t=0 (the PR-14 streaming
                               submission shape).
* ``priority_burst_trace``   — low-priority fleet filler plus a late
                               high-priority burst that cannot place:
                               the victim-selection shape.
"""

from __future__ import annotations

import dataclasses
import math
import random
from typing import Optional


@dataclasses.dataclass(frozen=True)
class SimTask:
    """One simulated task: everything placement, pricing, and victim
    selection need, and nothing else."""
    task_id: str
    arrival: float
    steps: int
    step_seconds: float
    priority: int = 0
    # Compile-cache identity digest (compilecache/manager.py
    # identity_key analog). None = nothing to compile / no affinity.
    cache_identity: Optional[str] = None
    # Cold-compile seconds paid when no node is warm for the
    # identity; a warm claim skips it (cache_hit).
    compile_seconds: float = 30.0
    # COMMITTED-checkpoint cadence in steps (0 = never): bounds the
    # replay rework a kill costs, exactly like workloads/checkpoint.
    ckpt_every: int = 0
    ckpt_seconds: float = 0.0
    gang_size: int = 1


def poisson_trace(seed: int, num_tasks: int, rate_per_second: float,
                  steps: int = 100, step_seconds: float = 0.5,
                  identities: int = 8,
                  identity_fraction: float = 0.7,
                  compile_seconds: float = 30.0,
                  ckpt_every: int = 20,
                  ckpt_seconds: float = 0.5,
                  priorities: tuple = (0,),
                  ) -> list[SimTask]:
    """Steady Poisson arrivals; ``identity_fraction`` of tasks carry
    one of ``identities`` compile-cache identities (the affinity
    policy's substrate), the rest are identity-less shell work."""
    rng = random.Random(seed)
    tasks = []
    t = 0.0
    for i in range(num_tasks):
        t += rng.expovariate(rate_per_second)
        identity = None
        if rng.random() < identity_fraction:
            identity = f"id-{rng.randrange(identities):04d}"
        tasks.append(SimTask(
            task_id=f"t{i:07d}", arrival=t,
            steps=max(1, int(rng.gauss(steps, steps * 0.2))),
            step_seconds=step_seconds,
            priority=priorities[rng.randrange(len(priorities))],
            cache_identity=identity,
            compile_seconds=compile_seconds,
            ckpt_every=ckpt_every, ckpt_seconds=ckpt_seconds))
    return tasks


def diurnal_arrivals(seed: int, num: int, day_seconds: float,
                     peak_rate: float, trough_rate: float,
                     ) -> list[float]:
    """Arrival times of an inhomogeneous Poisson process whose rate
    swings sinusoidally between trough and peak over a virtual day
    (thinning against the peak envelope). Factored out of
    ``diurnal_trace`` so the serving load generator
    (models/loadgen.py arrival="diurnal") replays the SAME arrival
    curve the fleet simulator schedules — one day/night shape across
    both layers, deterministic per (seed, params)."""
    rng = random.Random(seed)
    arrivals: list[float] = []
    t = 0.0
    while len(arrivals) < num:
        t += rng.expovariate(peak_rate)
        phase = math.sin(2.0 * math.pi * t / day_seconds)
        rate = trough_rate + (peak_rate - trough_rate) * \
            (0.5 + 0.5 * phase)
        if rng.random() * peak_rate > rate:
            continue
        arrivals.append(t)
    return arrivals


def diurnal_trace(seed: int, num_tasks: int, day_seconds: float,
                  peak_rate: float, trough_rate: float,
                  steps: int = 60, step_seconds: float = 0.5,
                  identities: int = 8,
                  compile_seconds: float = 30.0,
                  ckpt_every: int = 20,
                  ) -> list[SimTask]:
    """Sinusoidal arrival rate between trough and peak over a virtual
    day: the load swing that makes provisioning-vs-queueing badput a
    real trade. Arrivals come from ``diurnal_arrivals``; task
    attributes draw from an independent stream so attribute sampling
    cannot perturb the arrival curve (or vice versa)."""
    arrivals = diurnal_arrivals(seed, num_tasks, day_seconds,
                                peak_rate, trough_rate)
    rng = random.Random((seed << 1) ^ 0x5eed)
    tasks = []
    for i, t in enumerate(arrivals):
        identity = f"id-{rng.randrange(identities):04d}" \
            if rng.random() < 0.7 else None
        tasks.append(SimTask(
            task_id=f"t{i:07d}", arrival=t,
            steps=max(1, int(rng.gauss(steps, steps * 0.2))),
            step_seconds=step_seconds,
            cache_identity=identity,
            compile_seconds=compile_seconds,
            ckpt_every=ckpt_every, ckpt_seconds=0.5))
    return tasks


def scheduler_scale_trace(num_tasks: int = 1_000_000,
                          task_seconds: float = 1.0,
                          submit_rate: float = 50_000.0,
                          ) -> list[SimTask]:
    """The scheduler scale drill's shape (chaos/drill.py): up to
    10^6 tiny identity-less tasks streamed in one bulk submission
    (arrivals paced at the measured streaming-submission rate).
    Deterministic without a seed — the shape has no randomness to
    begin with."""
    return [SimTask(task_id=f"t{i:07d}",
                    arrival=i / submit_rate,
                    steps=1, step_seconds=task_seconds,
                    cache_identity=None, compile_seconds=0.0)
            for i in range(num_tasks)]


def priority_burst_trace(seed: int, filler_tasks: int,
                         burst_tasks: int, burst_at: float,
                         filler_steps: int = 200,
                         step_seconds: float = 0.5,
                         ckpt_every: int = 50,
                         ) -> list[SimTask]:
    """Low-priority long-running filler saturates the fleet; a
    high-priority burst arrives at ``burst_at`` and cannot place —
    the preemption sweep must elect victims, which is where the
    goodput-cost victim policy earns (or fails to earn) its keep.
    Half the filler checkpoints on cadence (cheap victims), half
    never commits (expensive victims)."""
    rng = random.Random(seed)
    tasks = []
    for i in range(filler_tasks):
        cadenced = i % 2 == 0
        tasks.append(SimTask(
            task_id=f"lo{i:06d}",
            arrival=rng.uniform(0.0, 5.0),
            steps=filler_steps, step_seconds=step_seconds,
            priority=0,
            cache_identity=f"id-{rng.randrange(8):04d}",
            compile_seconds=20.0,
            ckpt_every=ckpt_every if cadenced else 0,
            ckpt_seconds=0.3 if cadenced else 0.0))
    for i in range(burst_tasks):
        tasks.append(SimTask(
            task_id=f"hi{i:06d}",
            arrival=burst_at + rng.uniform(0.0, 2.0),
            steps=20, step_seconds=step_seconds, priority=5,
            cache_identity=None, compile_seconds=5.0))
    return tasks

"""Scenario registry: named (trace, chaos schedule, fleet shape)
bundles the CLI, bench, and tests share.

A scenario is a pure builder ``(seed, nodes, tasks) -> run_sim
kwargs`` — same arguments, same simulation, byte-identical report.
The chaos inventory (chaos/plan.py ``INJECTION_KINDS``) is fully
expressible as scenario schedules: ``KIND_ADAPTERS`` maps every
injection kind to the simulator method that applies it in virtual
time (tests/test_names_consistency.py asserts the mapping covers the
inventory, minus ``SIM_EXCLUDED_KINDS``).

Scenario schema (what a builder returns, passed to
``simulator.run_sim``)::

    {"trace":        list[SimTask],   # sim/traces.py generators
     "nodes":        int,             # initial fleet width
     "slots_per_node": int,
     "injections":   tuple[Injection, ...],  # chaos schedule
     "autoscale":    bool,            # enable the autoscale tick
     "min_nodes"/"max_nodes"/"provision_seconds": fleet limits}
"""

from __future__ import annotations

from typing import Callable

from batch_shipyard_tpu.chaos.plan import ChaosPlan, INJECTION_KINDS
from batch_shipyard_tpu.sched.policy import PolicyKnobs
from batch_shipyard_tpu.sim import traces
from batch_shipyard_tpu.sim.simulator import FleetSimulator

# Every batch-pool INJECTION_KINDS entry maps to the simulator
# adapter that applies it in virtual time (the serving kinds are
# excluded below — see SIM_EXCLUDED_KINDS).
KIND_ADAPTERS: dict[str, Callable] = {
    "store_delay": FleetSimulator.chaos_store_delay,
    "store_error": FleetSimulator.chaos_store_error,
    "heartbeat_blackout": FleetSimulator.chaos_heartbeat_blackout,
    "task_kill": FleetSimulator.chaos_task_kill,
    "task_wedge": FleetSimulator.chaos_task_wedge,
    "node_preempt": FleetSimulator.chaos_node_preempt,
    "node_preempt_notice": FleetSimulator.chaos_node_preempt_notice,
    "victim_ignore_notice":
        FleetSimulator.chaos_victim_ignore_notice,
    "host_loss_resize": FleetSimulator.chaos_host_loss_resize,
    "pool_capacity_loss": FleetSimulator.chaos_pool_capacity_loss,
    "store_outage": FleetSimulator.chaos_store_outage,
    "leader_partition": FleetSimulator.chaos_leader_partition,
    "agent_restart": FleetSimulator.chaos_agent_restart,
}

# Injection kinds with no sim adapter (the consistency test requires
# every INJECTION_KINDS entry to appear in exactly one of
# KIND_ADAPTERS / SIM_EXCLUDED_KINDS). The serving kinds target a
# serving fleet — HTTP replicas + a router, live token streams — not
# a batch pool; this simulator models scheduler/fleet dynamics, so
# they are drilled live instead (chaos/serving_drill.py,
# docs/37-serving-resilience.md).
SIM_EXCLUDED_KINDS: tuple = ("replica_kill", "replica_drain_notice",
                             "router_restart")

assert set(KIND_ADAPTERS) | set(SIM_EXCLUDED_KINDS) >= \
    set(INJECTION_KINDS)

# Mean service seconds of the steady/preemption-wave task shape
# (steps * step_seconds), used to size arrival rates to ~80% fleet
# utilization so queues neither explode nor stay empty.
_STEADY_STEPS = 100
_STEADY_STEP_SECONDS = 0.5


def _steady_rate(nodes: int, slots: int,
                 utilization: float = 0.65) -> float:
    service = _STEADY_STEPS * _STEADY_STEP_SECONDS
    return nodes * slots * utilization / service


def steady(seed: int, nodes: int, tasks: int) -> dict:
    """Steady Poisson arrivals at ~65% of bare-service utilization —
    sized so the queue stays SHORT even while compiles inflate
    effective service time (an overloaded queue ages every task past
    the affinity window and no placement policy can help it).

    One slot per node throughout (the TPU training shape): the
    goodput engine prices PER-NODE timelines, so one slot per node
    keeps one task's span from hiding behind a slot-mate's on the
    same timeline."""
    slots = 1
    return {
        "trace": traces.poisson_trace(
            seed, tasks, _steady_rate(nodes, slots),
            steps=_STEADY_STEPS,
            step_seconds=_STEADY_STEP_SECONDS,
            identities=max(4, nodes // 4), identity_fraction=0.8,
            compile_seconds=30.0, ckpt_every=20, ckpt_seconds=0.5),
        "nodes": nodes, "slots_per_node": slots}


def diurnal(seed: int, nodes: int, tasks: int) -> dict:
    """Sinusoidal day/night load with autoscale enabled: the
    provisioning-vs-queueing badput trade the goodput autoscale
    policy exists for."""
    slots = 1
    peak = _steady_rate(nodes, slots, utilization=1.1)
    return {
        "trace": traces.diurnal_trace(
            seed, tasks, day_seconds=3600.0, peak_rate=peak,
            trough_rate=0.15 * peak, steps=60,
            step_seconds=_STEADY_STEP_SECONDS,
            identities=max(4, nodes // 2), compile_seconds=30.0,
            ckpt_every=20),
        "nodes": max(1, nodes // 4), "slots_per_node": slots,
        "autoscale": True, "min_nodes": max(1, nodes // 8),
        "max_nodes": nodes, "provision_seconds": 120.0,
        # Knobs matched to the trace shape: the autoscale model's
        # backlog estimate uses avg_task_seconds, and this trace's
        # tasks run 60 steps x 0.5s.
        "knobs": PolicyKnobs(avg_task_seconds=30.0)}


def scheduler_scale(seed: int, nodes: int, tasks: int) -> dict:
    """Shaped like chaos/drill.py's scheduler scale drill: one
    streamed bulk submission of tiny identity-less tasks (10^6 at
    the drill's default) — the queueing/claim-throughput regime, no
    compile or checkpoint legs. Deterministic regardless of seed."""
    del seed
    return {
        "trace": traces.scheduler_scale_trace(
            num_tasks=tasks, task_seconds=1.0),
        "nodes": nodes, "slots_per_node": 1}


def preemption_wave(seed: int, nodes: int, tasks: int) -> dict:
    """THE chaos-schedule scenario: steady load, then a provider
    preemption wave takes out 30% of the fleet mid-run — warm
    compile state destroyed, uncommitted steps replayed, a
    recovery-leg spike. Policies differ in how much of that badput
    they buy back."""
    base = steady(seed, nodes, tasks)
    plan = ChaosPlan.preemption_wave(
        seed, at=400.0, num_nodes=nodes,
        fraction=0.3, revive_after=60.0)
    return dict(base, injections=plan.injections)


def priority_burst(seed: int, nodes: int, tasks: int) -> dict:
    """Fleet saturated with low-priority fillers (half cadenced
    committers = cheap victims, half never-commit = expensive), then
    a high-priority burst that cannot place: the preemption sweep
    must elect victims, which is where goodput-cost victim selection
    shows up as avoided replay rework."""
    # The burst must be NARROWER than the fleet: a burst as wide as
    # the node count evicts every runner under any ordering and no
    # victim-selection policy can differ.
    burst = max(1, min(tasks // 10, nodes // 3))
    filler = max(1, tasks - burst)
    return {
        "trace": traces.priority_burst_trace(
            seed, filler_tasks=filler, burst_tasks=burst,
            burst_at=60.0, filler_steps=200,
            step_seconds=_STEADY_STEP_SECONDS, ckpt_every=50),
        "nodes": nodes, "slots_per_node": 1}


def chaos_soup(seed: int, nodes: int, tasks: int) -> dict:
    """Every batch-pool injection kind in one schedule (the full
    sim-expressible inventory as a scenario) — the smoke proof that
    every non-excluded chaos kind works in virtual time. The serving
    kinds (SIM_EXCLUDED_KINDS) are drilled live instead."""
    base = steady(seed, nodes, tasks)
    plan = ChaosPlan.generate(
        seed, duration=600.0, num_nodes=nodes,
        kinds=tuple(k for k in INJECTION_KINDS
                    if k not in SIM_EXCLUDED_KINDS),
        injections_per_kind=2)
    return dict(base, injections=plan.injections)


SCENARIOS: dict[str, Callable] = {
    "steady": steady,
    "diurnal": diurnal,
    "scheduler_scale": scheduler_scale,
    "preemption_wave": preemption_wave,
    "priority_burst": priority_burst,
    "chaos_soup": chaos_soup,
}

DESCRIPTIONS: dict[str, str] = {
    name: (fn.__doc__ or "").strip().split("\n")[0]
    for name, fn in SCENARIOS.items()
}


def build(name: str, seed: int, nodes: int, tasks: int) -> dict:
    if name not in SCENARIOS:
        raise KeyError(
            f"unknown scenario {name!r}; have "
            f"{', '.join(sorted(SCENARIOS))}")
    return SCENARIOS[name](seed, nodes, tasks)

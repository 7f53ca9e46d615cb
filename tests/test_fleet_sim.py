"""Fleet-simulator + shared-policy tests (ISSUE 17).

Three contracts pinned here:

1. **Determinism** — same (seed, trace, policy) => byte-identical
   report and equal fingerprint; this is what makes a sim policy
   delta attributable to the policy instead of to noise, and what
   the `sim-wall-clock` analyzer rule protects statically.
2. **Policy behavior** — the tier-1 smoke (<=200 virtual nodes,
   seconds of wall time) shows warm-cache claim affinity beating the
   baseline bundle on the steady scenario, priced by the production
   goodput engine with an exact partition; a slow-marked sweep runs
   the >=2,000-node shape.
3. **No forked copies** — the sim prices the SAME pure functions
   (sched/policy.py) the live agent claim path, preemption sweep,
   and pool autoscaler import; the decision code is defined exactly
   once.
"""

import ast
import json
import pathlib

import pytest

from batch_shipyard_tpu.agent import progress
from batch_shipyard_tpu.sched import policy as sched_policy
from batch_shipyard_tpu.sim import scenarios as sim_scenarios
from batch_shipyard_tpu.sim import simulator as sim_mod

PACKAGE = pathlib.Path(sched_policy.__file__).resolve().parent.parent
REPO_ROOT = PACKAGE.parent


# --------------------------- policy units ---------------------------

def test_claim_score_prices_cold_health_and_backoff():
    """A warm healthy node is a perfect claim (0.0); every debit —
    cold compile, poor health, recent failures — adds seconds, so
    scores compose by addition and order totally."""
    knobs = sched_policy.PolicyKnobs()
    assert sched_policy.claim_score(warm=True) == 0.0
    cold = sched_policy.claim_score(warm=False)
    assert cold == knobs.warm_cache_bonus_seconds
    # No identity advertised -> no cold-compile leg to price.
    assert sched_policy.claim_score(warm=False,
                                    has_identity=False) == 0.0
    sick = sched_policy.claim_score(warm=True, health=0.5)
    assert sick == pytest.approx(0.5 * knobs.health_debit_seconds)
    flaky = sched_policy.claim_score(warm=True, recent_failures=2)
    assert flaky == 2 * knobs.backoff_debit_seconds
    # The failure debit caps at 4: backoff cannot blacklist forever.
    assert sched_policy.claim_score(warm=True, recent_failures=99) \
        == sched_policy.claim_score(warm=True, recent_failures=4)


def test_should_defer_claim_window_never_starves():
    """A costly claim on a YOUNG task defers back to the queue; past
    the affinity window the claim always proceeds — affinity trades
    queueing seconds for compile seconds, never starvation."""
    knobs = sched_policy.PolicyKnobs()
    costly = sched_policy.claim_score(warm=False, knobs=knobs)
    assert sched_policy.should_defer_claim(costly, 0.0, knobs)
    assert not sched_policy.should_defer_claim(
        costly, knobs.claim_affinity_wait_seconds, knobs)
    assert not sched_policy.should_defer_claim(0.0, 0.0, knobs)


def test_victim_cost_orders_committed_cold_below_warm_uncommitted():
    """The drill shape: a task that just committed and holds no warm
    identity is the cheap victim; a warm task far past its last
    commit is expensive. Gang width scales the whole cost (every
    instance replays)."""
    cheap = sched_policy.victim_cost(
        warm=False, steps_since_commit=0, step_seconds=0.5)
    costly = sched_policy.victim_cost(
        warm=True, steps_since_commit=60, step_seconds=0.5)
    assert cheap == 0.0 < costly
    assert sched_policy.victim_cost(
        warm=True, steps_since_commit=60, step_seconds=0.5,
        gang_size=4) == pytest.approx(4 * costly)


def test_victim_cost_from_row_prices_synced_hints():
    """The live-row pricer reads the sched_hints column the agent
    mirrors from the workload's hints file; a hint-less task prices
    at 0.0 and falls back to the (priority, cost, task_id)
    tie-break."""
    from batch_shipyard_tpu.state import names
    assert sched_policy.victim_cost_from_row({}) == 0.0
    row = {names.TASK_COL_SCHED_HINTS: {
        "step": 80, "ckpt_step": 20, "step_seconds": 0.5,
        "cache_identity": "digest"}}
    expected = sched_policy.victim_cost(
        warm=True, steps_since_commit=60, step_seconds=0.5)
    assert sched_policy.victim_cost_from_row(row) == \
        pytest.approx(expected)
    # Sort key: priority dominates, then cost, then task id — never
    # scan order.
    keys = sorted([
        sched_policy.victim_sort_key(10, 0.0, "a"),
        sched_policy.victim_sort_key(0, 99.0, "z"),
        sched_policy.victim_sort_key(0, 0.0, "b"),
        sched_policy.victim_sort_key(0, 0.0, "a"),
    ])
    assert keys == [(0, 0.0, "a"), (0, 0.0, "b"), (0, 99.0, "z"),
                    (10, 0.0, "a")]


def test_record_sched_hints_round_trip(tmp_path, monkeypatch):
    """Workload-side publication: partial updates merge (a
    checkpointer knows ckpt_step, the step loop knows step), the
    write is atomic tmp+rename, and no env var means no-op."""
    hints_file = tmp_path / "hints.json"
    monkeypatch.setenv(progress.SCHED_HINTS_FILE_ENV,
                       str(hints_file))
    progress.record_sched_hints(step=5, step_seconds=0.5,
                                cache_identity="digest")
    progress.record_sched_hints(ckpt_step=5)
    progress.record_sched_hints(step=9)
    assert progress.read_sched_hints(str(hints_file)) == {
        "step": 9, "ckpt_step": 5, "step_seconds": 0.5,
        "cache_identity": "digest"}
    monkeypatch.delenv(progress.SCHED_HINTS_FILE_ENV)
    progress.record_sched_hints(step=99)  # hints disabled: no-op
    assert progress.read_sched_hints(str(hints_file))["step"] == 9


def test_autoscale_target_marginal_trade_and_damped_drain():
    knobs = sched_policy.PolicyKnobs()
    # Deep backlog: scale up past the busy floor, and the reason
    # names the trade.
    target, why = sched_policy.autoscale_target(
        pending_tasks=500, active_tasks=10, current_nodes=10,
        slots_per_node=1, knobs=knobs)
    assert target > 10 and "provisioning" in why
    # Empty queue: drain TOWARD the busy floor at most 10% per call
    # (a cliff would churn provisioning on the next burst).
    target, why = sched_policy.autoscale_target(
        pending_tasks=0, active_tasks=10, current_nodes=100,
        slots_per_node=1, knobs=knobs)
    assert target == 90 and "drain" in why
    # Never below the busy floor.
    target, _ = sched_policy.autoscale_target(
        pending_tasks=0, active_tasks=50, current_nodes=52,
        slots_per_node=1, knobs=knobs)
    assert target >= 50
    # A trickle inside tolerance is not worth provisioning for.
    target, why = sched_policy.autoscale_target(
        pending_tasks=1, active_tasks=4, current_nodes=4,
        slots_per_node=1, knobs=knobs)
    assert target == 4 and "tolerance" in why


# --------------------------- determinism ----------------------------

def test_sim_report_byte_identical_for_same_seed_trace_policy():
    """THE determinism contract: two fresh simulator instances over
    the same (seed, trace, policy) produce byte-identical canonical
    JSON (and therefore equal fingerprints); a different seed moves
    the fingerprint. This holds under `-p no:randomly` and any test
    ordering because the sim owns its RNG and its clock."""
    kwargs = sim_scenarios.build("steady", seed=3, nodes=50,
                                 tasks=400)
    first = sim_mod.run_sim(policy="combined", **kwargs)
    again = sim_mod.run_sim(
        policy="combined",
        **sim_scenarios.build("steady", seed=3, nodes=50, tasks=400))
    assert json.dumps(first, sort_keys=True) == \
        json.dumps(again, sort_keys=True)
    assert first["fingerprint"] == again["fingerprint"]
    other = sim_mod.run_sim(
        policy="combined",
        **sim_scenarios.build("steady", seed=4, nodes=50, tasks=400))
    assert other["fingerprint"] != first["fingerprint"]
    assert first["partition_exact"], first["partition_error"]


# ------------------------- tier-1 smoke -----------------------------

def test_sim_smoke_affinity_beats_baseline_on_steady():
    """The tier-1 policy proof at smoke scale (100 virtual nodes,
    1,000 tasks — seconds of wall time): warm-cache claim affinity
    converts compile badput into a higher goodput ratio than the
    baseline bundle on the same seed, and both partitions are exact
    (productive + badput + overlapped == node-seconds wall)."""
    reports = {
        name: sim_mod.run_sim(
            policy=name,
            **sim_scenarios.build("steady", seed=0, nodes=100,
                                  tasks=1000))
        for name in ("baseline", "affinity")}
    for rep in reports.values():
        assert rep["partition_exact"], rep["partition_error"]
        assert rep["scheduler"]["tasks_completed"] == 1000
    compared = sim_mod.compare(reports)
    delta = compared["affinity"]["delta_vs_baseline"]
    assert delta["goodput_ratio_delta"] > 0.0
    # The win is specifically a compile-badput conversion.
    assert delta["badput_seconds_delta"].get("compile", 0.0) < 0.0
    assert reports["affinity"]["fingerprint"] != \
        reports["baseline"]["fingerprint"]


def test_sim_chaos_preemption_wave_stays_partition_exact():
    """The chaos inventory as scenario schedules: a preemption wave
    (seeded provider kills mid-run) exercises replay + rescheduling
    in virtual time, completes every task, and the goodput partition
    stays exact through the churn."""
    rep = sim_mod.run_sim(
        policy="baseline",
        **sim_scenarios.build("preemption_wave", seed=1, nodes=60,
                              tasks=400))
    assert rep["scheduler"]["preemptions"] > 0
    assert rep["scheduler"]["tasks_completed"] == 400
    assert rep["partition_exact"], rep["partition_error"]
    assert rep["goodput"]["badput_seconds"].get(
        "preemption_recovery", 0.0) > 0.0


# ----------------------- no forked copies ---------------------------

def test_policy_functions_defined_only_in_sched_policy():
    """The decision functions exist exactly once, in
    sched/policy.py — the sim prices the same code the live paths
    run, so a sim delta is a statement about production behavior."""
    owned = {"claim_score", "should_defer_claim", "victim_cost",
             "victim_cost_from_row", "victim_sort_key",
             "autoscale_target"}
    definers: dict = {name: [] for name in owned}
    for path in sorted(PACKAGE.rglob("*.py")):
        tree = ast.parse(path.read_text(encoding="utf-8"),
                         filename=str(path))
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)) and \
                    node.name in owned:
                definers[node.name].append(
                    str(path.relative_to(PACKAGE.parent)))
    for name, sites in definers.items():
        assert sites == ["batch_shipyard_tpu/sched/policy.py"], (
            f"{name} must be defined exactly once in "
            f"sched/policy.py, found {sites}")


def test_live_paths_import_the_shared_policy_module():
    """Claim path + preemption sweep (agent/node_agent.py), pool
    autoscaler (pool/autoscale.py), and the simulator all import
    sched.policy — no consumer carries a private copy."""
    for rel in ("agent/node_agent.py", "pool/autoscale.py",
                "sim/simulator.py"):
        src = (PACKAGE / rel).read_text(encoding="utf-8")
        assert "batch_shipyard_tpu.sched import policy" in src, (
            f"{rel} does not import the shared policy module")
    agent_src = (PACKAGE / "agent" / "node_agent.py").read_text(
        encoding="utf-8")
    for call in ("claim_score", "should_defer_claim",
                 "victim_cost_from_row", "victim_sort_key"):
        assert f"sched_policy.{call}(" in agent_src, (
            f"node_agent.py does not call sched_policy.{call}")
    autoscale_src = (PACKAGE / "pool" / "autoscale.py").read_text(
        encoding="utf-8")
    assert "sched_policy.autoscale_target(" in autoscale_src


# --------------------------- CLI surface ----------------------------

def test_sim_actions_run_scenarios_compare():
    """The `shipyard sim` actions: scenarios inventories every
    scenario + policy bundle; run returns a fingerprinted report;
    compare always includes the baseline control and prices deltas
    against it."""
    from batch_shipyard_tpu import fleet
    inventory = fleet.action_sim_scenarios(None, raw=True)
    assert set(inventory["scenarios"]) == \
        set(sim_scenarios.SCENARIOS)
    assert set(inventory["policies"]) == set(sched_policy.POLICIES)
    report = fleet.action_sim_run(None, scenario="steady",
                                  policy="baseline", seed=0,
                                  nodes=20, tasks=60, raw=True)
    assert report["fingerprint"] and report["partition_exact"]
    summary = fleet.action_sim_compare(None, scenario="steady",
                                       policies=("affinity",),
                                       seed=0, nodes=20, tasks=60,
                                       raw=True)
    assert set(summary["runs"]) == {"baseline", "affinity"}
    assert "goodput_ratio_delta" in \
        summary["policies"]["affinity"]


# ------------------------- fleet scale (slow) -----------------------

@pytest.mark.slow
def test_sim_fleet_scale_sweep_2000_nodes():
    """The bench shape at tier-2: >=2,000 virtual nodes, every task
    completed, partition exact, and still byte-deterministic (the
    fingerprint is stable across two fresh runs)."""
    build = lambda: sim_scenarios.build(  # noqa: E731
        "steady", seed=1, nodes=2000, tasks=20_000)
    first = sim_mod.run_sim(policy="combined", **build())
    assert first["nodes"] >= 2000
    assert first["scheduler"]["tasks_completed"] == 20_000
    assert first["partition_exact"], first["partition_error"]
    again = sim_mod.run_sim(policy="combined", **build())
    assert again["fingerprint"] == first["fingerprint"]

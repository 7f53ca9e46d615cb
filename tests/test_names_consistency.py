"""Static consistency — now a thin wrapper over `shipyard lint`.

The table/event/span/state/CLI-action AST scans that used to live
here are registered analyzer rules (batch_shipyard_tpu/analysis/,
PR 11); each historical test keeps its name and coverage but runs
the corresponding rule over the real tree, so tier-1 sees the same
gates while the CLI (`shipyard lint`) and tests/test_analysis.py
share one implementation. Checks with no analyzer analog (committed
bench artifacts, tools/ cross-file wiring) stay native below.
"""

import ast
import pathlib

from batch_shipyard_tpu import analysis
from batch_shipyard_tpu.state import names

PACKAGE = pathlib.Path(names.__file__).resolve().parent.parent

_CTX = analysis.AnalysisContext.from_tree()


def _run(rule_id: str) -> list:
    """Active findings of one analyzer rule over the real tree
    (inline-suppressed sites excluded, like the lint gate)."""
    active, _ = analysis.run_rules(_CTX, [rule_id])
    return active


def _fail_lines(findings) -> str:
    return "\n".join(f.render() for f in findings)


def test_declared_table_values_are_unique():
    declared = {a for a in dir(names) if a.startswith("TABLE_")}
    values = {getattr(names, a) for a in declared}
    assert len(values) == len(declared), (
        "two TABLE_* constants in state/names.py share a value")


def test_every_table_literal_is_declared():
    findings = _run("registry-table-undeclared")
    assert not findings, _fail_lines(findings)


def test_goodput_table_declared():
    # The event log's table rides the same registry as every other
    # coordination surface.
    assert names.TABLE_GOODPUT == "goodput"
    assert hasattr(names, "TABLE_GOODPUT")
    # PR 11: the schedule table joined the registry when the analyzer
    # caught its hand-rolled literal.
    assert names.TABLE_JOBSCHEDULES == "jobschedules"


def test_goodput_program_constants_are_declared():
    """Every event-kind constant referenced through a goodput/events
    alias resolves there and is registered in EVENT_KINDS (analyzer
    rule goodput-kind-undeclared, generalizing the old PROGRAM_*
    scan)."""
    findings = _run("goodput-kind-undeclared")
    assert not findings, _fail_lines(findings)


def test_task_state_literals_come_from_the_registry():
    findings = _run("registry-state-literal")
    assert not findings, _fail_lines(findings)


def test_quarantine_and_health_names_declared():
    """PR 5's new vocabulary rides the registry: the quarantined task
    state is terminal (and a TASK_STATE), and the node health columns
    are single-sourced."""
    assert names.TASK_STATE_QUARANTINED == "quarantined"
    assert names.TASK_STATE_QUARANTINED in names.TASK_STATES
    assert names.TASK_STATE_QUARANTINED in names.TERMINAL_TASK_STATES
    assert set(names.TERMINAL_TASK_STATES) <= set(names.TASK_STATES)
    assert names.NODE_COL_HEALTH == "health"
    assert names.NODE_COL_QUARANTINED == "quarantined"


def test_task_and_backoff_event_constants_are_declared():
    """The retry supervisor's TASK_RETRY/TASK_BACKOFF (and every
    other event constant) are covered by the undeclared-kind rule;
    the backoff pricing invariant is covered by the unpriced-kind
    rule plus the direct asserts."""
    from batch_shipyard_tpu.goodput import accounting
    from batch_shipyard_tpu.goodput import events as gp_events
    findings = _run("goodput-kind-undeclared")
    findings += _run("goodput-kind-unpriced")
    assert not findings, _fail_lines(findings)
    assert accounting._KIND_CATEGORY[
        gp_events.TASK_BACKOFF] == "backoff"
    assert "backoff" in accounting.BADPUT_CATEGORIES
    # Server-side task-factory expansion is priced as its own
    # scheduling-badput category (the 10^6 bench's submit leg).
    assert accounting._KIND_CATEGORY[
        gp_events.TASK_EXPANSION] == "expansion"
    assert "expansion" in accounting.BADPUT_CATEGORIES


def test_preemption_and_resize_names_declared():
    """PR 10's vocabulary: preempted is NON-terminal and claimable;
    the TASK_PREEMPT_*/GANG_RESIZE kinds are declared+registered
    (rule), actually referenced at emit sites (native scan — dead
    registry check), and the recovery leg is priced."""
    from batch_shipyard_tpu.goodput import accounting
    from batch_shipyard_tpu.goodput import events as gp_events
    from batch_shipyard_tpu.trace import spans as trace_spans
    assert names.TASK_STATE_PREEMPTED == "preempted"
    assert names.TASK_STATE_PREEMPTED in names.TASK_STATES
    assert names.TASK_STATE_PREEMPTED not in \
        names.TERMINAL_TASK_STATES
    assert names.TASK_STATE_PREEMPTED in names.CLAIMABLE_TASK_STATES
    assert set(names.CLAIMABLE_TASK_STATES) <= set(names.TASK_STATES)
    findings = _run("goodput-kind-undeclared")
    assert not findings, _fail_lines(findings)
    # Every kind of the family is actually referenced at an emit
    # site — a declared-but-never-emitted kind is dead registry.
    event_attrs = {"TASK_PREEMPT_NOTICE", "TASK_PREEMPT_EXIT",
                   "TASK_PREEMPT_RECOVERY", "GANG_RESIZE"}
    referenced = set()
    for src in _CTX.python_files:
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Attribute) and \
                    node.attr in event_attrs:
                referenced.add(node.attr)
    assert event_attrs <= referenced, event_attrs - referenced
    assert accounting._KIND_CATEGORY[
        gp_events.TASK_PREEMPT_RECOVERY] == "preemption_recovery"
    assert "preemption_recovery" in accounting.BADPUT_CATEGORIES
    assert trace_spans.SPAN_PREEMPT in trace_spans.SPAN_KINDS
    assert trace_spans.SPAN_GANG_RESIZE in trace_spans.SPAN_KINDS


def test_eviction_and_migration_names_declared():
    """PR 12's vocabulary: evicted is NON-terminal and claimable
    like preempted; the TASK_EVICTED / TASK_EVICTION_RECOVERY /
    GANG_MIGRATE kinds are declared+registered (rule), actually
    referenced at emit sites (native scan — dead registry check),
    and the eviction/migration legs are priced as their own badput
    categories. The evict/gang_migrate spans ride SPAN_KINDS."""
    from batch_shipyard_tpu.goodput import accounting
    from batch_shipyard_tpu.goodput import events as gp_events
    from batch_shipyard_tpu.trace import spans as trace_spans
    assert names.TASK_STATE_EVICTED == "evicted"
    assert names.TASK_STATE_EVICTED in names.TASK_STATES
    assert names.TASK_STATE_EVICTED not in \
        names.TERMINAL_TASK_STATES
    assert names.TASK_STATE_EVICTED in names.CLAIMABLE_TASK_STATES
    findings = _run("goodput-kind-undeclared")
    findings += _run("goodput-kind-unpriced")
    assert not findings, _fail_lines(findings)
    event_attrs = {"TASK_EVICTED", "TASK_EVICTION_RECOVERY",
                   "GANG_MIGRATE"}
    referenced = set()
    for src in _CTX.python_files:
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Attribute) and \
                    node.attr in event_attrs:
                referenced.add(node.attr)
    assert event_attrs <= referenced, event_attrs - referenced
    assert accounting._KIND_CATEGORY[
        gp_events.TASK_EVICTION_RECOVERY] == "eviction"
    assert accounting._KIND_CATEGORY[
        gp_events.GANG_MIGRATE] == "migration"
    assert "eviction" in accounting.BADPUT_CATEGORIES
    assert "migration" in accounting.BADPUT_CATEGORIES
    assert trace_spans.SPAN_EVICT in trace_spans.SPAN_KINDS
    assert trace_spans.SPAN_GANG_MIGRATE in trace_spans.SPAN_KINDS


def test_fleet_elasticity_chaos_kinds_wired():
    """The three PR 12 chaos kinds are registered in
    INJECTION_KINDS (validation + --kinds help, which derives from
    it), excluded from the generic default schedule (a single-pool
    generic drill cannot recover from pool_capacity_loss by
    construction), actually APPLIED by the injector, and actually
    requested by at least one drill — a kind nothing injects is
    dead vocabulary."""
    from batch_shipyard_tpu.chaos.plan import (
        DEFAULT_DRILL_KINDS, INJECTION_KINDS)
    new_kinds = {"victim_ignore_notice", "host_loss_resize",
                 "pool_capacity_loss"}
    assert new_kinds <= set(INJECTION_KINDS)
    assert not new_kinds & set(DEFAULT_DRILL_KINDS)
    assert set(DEFAULT_DRILL_KINDS) <= set(INJECTION_KINDS)
    injectors_src = (PACKAGE / "chaos" / "injectors.py").read_text(
        encoding="utf-8")
    drill_src = (PACKAGE / "chaos" / "drill.py").read_text(
        encoding="utf-8")
    for kind in sorted(new_kinds):
        assert f'"{kind}"' in injectors_src, (
            f"chaos kind {kind} has no injector")
        assert f'"{kind}"' in drill_src, (
            f"chaos kind {kind} is not injected by any drill")
    # The rendered --kinds help really names them (derived from
    # INJECTION_KINDS; the wiring rule keeps it derived).
    import click

    from batch_shipyard_tpu.cli import main as cli_main
    ctx = click.Context(cli_main.chaos_plan, info_name="plan")
    rendered = "".join(cli_main.chaos_plan.get_help(ctx).split())
    for kind in sorted(new_kinds):
        assert kind in rendered


def test_fleet_elasticity_dispatched_and_rendered():
    """The fleet-elasticity drills are wired end to end: bench.py
    dispatches the fleet_elasticity workload, benchgen renders the
    committed BENCH_fleet_elasticity.json artifact, and the artifact
    records all three drills passing."""
    import json
    bench_src = (PACKAGE.parent / "bench.py").read_text(
        encoding="utf-8")
    assert '"fleet_elasticity" in workloads' in bench_src
    benchgen_src = (PACKAGE.parent / "tools" / "benchgen.py"
                    ).read_text(encoding="utf-8")
    assert "BENCH_fleet_elasticity.json" in benchgen_src
    artifact = PACKAGE.parent / "BENCH_fleet_elasticity.json"
    assert artifact.exists(), (
        "BENCH_fleet_elasticity.json not committed — run "
        "`python bench.py --workloads fleet_elasticity`")
    data = json.loads(artifact.read_text(
        encoding="utf-8"))["fleet_elasticity"]
    assert data["all_passed"] is True
    assert set(data["drills"]) == {"eviction", "host_resize",
                                   "migration"}
    for entry in data["drills"].values():
        assert entry["passed"] is True
        assert entry["invariants_checked"]
    assert data.get("cpu_marker") is True


def test_control_plane_vocabulary_declared():
    """ISSUE 13's vocabulary: the STORE_OUTAGE / TASK_ADOPTION kinds
    are declared+registered (rule), priced as their own badput
    categories, actually referenced at emit sites (native scan —
    dead registry check); SPAN_AGENT_RESTART rides SPAN_KINDS and is
    emitted; the leader-lease roles and key helpers exist."""
    from batch_shipyard_tpu.goodput import accounting
    from batch_shipyard_tpu.goodput import events as gp_events
    from batch_shipyard_tpu.state import leases as state_leases
    from batch_shipyard_tpu.trace import spans as trace_spans
    findings = _run("goodput-kind-undeclared")
    findings += _run("goodput-kind-unpriced")
    findings += _run("trace-span-undeclared")
    assert not findings, _fail_lines(findings)
    event_attrs = {"STORE_OUTAGE", "TASK_ADOPTION",
                   "SPAN_AGENT_RESTART"}
    referenced = set()
    for src in _CTX.python_files:
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Attribute) and \
                    node.attr in event_attrs:
                referenced.add(node.attr)
    assert event_attrs <= referenced, event_attrs - referenced
    assert accounting._KIND_CATEGORY[
        gp_events.STORE_OUTAGE] == "store_outage"
    assert accounting._KIND_CATEGORY[
        gp_events.TASK_ADOPTION] == "adoption"
    assert "store_outage" in accounting.BADPUT_CATEGORIES
    assert "adoption" in accounting.BADPUT_CATEGORIES
    assert trace_spans.SPAN_AGENT_RESTART in trace_spans.SPAN_KINDS
    # Leader-lease vocabulary: role registry + key helpers + the
    # heartbeat-published WAL backlog column.
    assert state_leases.ROLE_GANG_JANITOR in \
        state_leases.AGENT_LEADER_ROLES
    assert state_leases.ROLE_PREEMPT_SWEEP in \
        state_leases.AGENT_LEADER_ROLES
    assert names.leader_epoch_key("p", "r") == \
        names.leader_lease_key("p", "r") + ".epoch"
    assert names.NODE_COL_JOURNAL_BACKLOG == "journal_backlog"


def test_control_plane_chaos_kinds_wired():
    """The three ISSUE 13 chaos kinds are registered in
    INJECTION_KINDS (validation + --kinds help, which derives from
    it), excluded from the generic default schedule (a sustained
    outage without the resilient wrapper armed is unrecoverable by
    construction), actually APPLIED by the injector, and actually
    requested by at least one drill — a kind nothing injects is
    dead vocabulary. The three drill flags are rendered by the CLI
    help."""
    from batch_shipyard_tpu.chaos.plan import (
        DEFAULT_DRILL_KINDS, INJECTION_KINDS)
    new_kinds = {"store_outage", "leader_partition", "agent_restart"}
    assert new_kinds <= set(INJECTION_KINDS)
    assert not new_kinds & set(DEFAULT_DRILL_KINDS)
    injectors_src = (PACKAGE / "chaos" / "injectors.py").read_text(
        encoding="utf-8")
    drill_src = (PACKAGE / "chaos" / "drill.py").read_text(
        encoding="utf-8")
    for kind in sorted(new_kinds):
        assert f'"{kind}"' in injectors_src, (
            f"chaos kind {kind} has no injector")
        assert f'"{kind}"' in drill_src, (
            f"chaos kind {kind} is not injected by any drill")
    import click

    from batch_shipyard_tpu.cli import main as cli_main
    ctx = click.Context(cli_main.chaos_plan, info_name="plan")
    rendered = "".join(cli_main.chaos_plan.get_help(ctx).split())
    for kind in sorted(new_kinds):
        assert kind in rendered
    ctx = click.Context(cli_main.chaos_drill, info_name="drill")
    rendered = cli_main.chaos_drill.get_help(ctx)
    for flag in ("--outage", "--partition", "--restart"):
        assert flag in rendered, f"drill flag {flag} not wired"


def test_control_plane_dispatched_and_rendered():
    """The control-plane drills are wired end to end: bench.py
    dispatches the control_plane workload, benchgen renders the
    committed BENCH_control_plane.json artifact, and the artifact
    records all three drills passing."""
    import json
    bench_src = (PACKAGE.parent / "bench.py").read_text(
        encoding="utf-8")
    assert '"control_plane" in workloads' in bench_src
    benchgen_src = (PACKAGE.parent / "tools" / "benchgen.py"
                    ).read_text(encoding="utf-8")
    assert "BENCH_control_plane.json" in benchgen_src
    artifact = PACKAGE.parent / "BENCH_control_plane.json"
    assert artifact.exists(), (
        "BENCH_control_plane.json not committed — run "
        "`python bench.py --workloads control_plane`")
    data = json.loads(artifact.read_text(
        encoding="utf-8"))["control_plane"]
    assert data["all_passed"] is True
    assert set(data["drills"]) == {"store_outage",
                                   "leader_partition",
                                   "agent_restart"}
    for entry in data["drills"].values():
        assert entry["passed"] is True
        assert entry["invariants_checked"]
    assert data.get("cpu_marker") is True


def test_chaos_kinds_all_expressible_in_the_simulator():
    """ISSUE 17: every chaos injection kind maps to a simulator
    adapter (sim/scenarios.py KIND_ADAPTERS) or is explicitly listed
    in SIM_EXCLUDED_KINDS — a kind in neither set is a chaos mode
    the fleet simulator silently cannot model. The exclusion set
    holds exactly the serving kinds (replica/router), which target a
    serving fleet rather than a batch pool and are drilled live
    (chaos/serving_drill.py) instead."""
    from batch_shipyard_tpu.chaos.plan import INJECTION_KINDS
    from batch_shipyard_tpu.sim import scenarios as sim_scenarios
    unmapped = set(INJECTION_KINDS) - set(
        sim_scenarios.KIND_ADAPTERS) - set(
        sim_scenarios.SIM_EXCLUDED_KINDS)
    assert not unmapped, (
        f"chaos kinds with no sim adapter and no exclusion entry: "
        f"{sorted(unmapped)}")
    # No dead adapters either: every adapter key is a real kind.
    dead = set(sim_scenarios.KIND_ADAPTERS) - set(INJECTION_KINDS)
    assert not dead, f"sim adapters for unknown kinds: {sorted(dead)}"
    assert not set(sim_scenarios.SIM_EXCLUDED_KINDS) & set(
        sim_scenarios.KIND_ADAPTERS)


def test_policy_knobs_mirrored_in_settings_and_schema():
    """The sched_policy knob surface is single-sourced: every
    PolicyKnobs field (sched/policy.py) appears by NAME in
    SchedPolicySettings (config/settings.py) and in the pool.yaml
    schema's sched_policy block — a knob added in one place but not
    the others would silently fall back to defaults for every pool
    spec."""
    import dataclasses

    from batch_shipyard_tpu.config import settings as S
    from batch_shipyard_tpu.sched import policy as sched_policy
    knob_fields = {f.name for f in
                   dataclasses.fields(sched_policy.PolicyKnobs)}
    settings_fields = {f.name for f in
                       dataclasses.fields(S.SchedPolicySettings)}
    missing = knob_fields - settings_fields
    assert not missing, (
        f"PolicyKnobs fields absent from SchedPolicySettings: "
        f"{sorted(missing)}")
    schema_src = (PACKAGE / "config" / "schemas" / "pool.yaml"
                  ).read_text(encoding="utf-8")
    for field in sorted(knob_fields):
        assert f"{field}:" in schema_src, (
            f"pool.yaml schema sched_policy block lacks {field}")
    # knobs_from_settings round-trips a fully-populated settings
    # object field-for-field (None falls back to defaults).
    populated = S.SchedPolicySettings(
        claim_scoring=True,
        **{name: 7.0 for name in knob_fields})
    knobs = sched_policy.knobs_from_settings(populated)
    assert all(getattr(knobs, name) == 7.0 for name in knob_fields)
    defaults = sched_policy.knobs_from_settings(None)
    assert defaults == sched_policy.PolicyKnobs()


def test_fleet_sim_dispatched_and_rendered():
    """The fleet-simulator policy proof is wired end to end: bench.py
    dispatches the fleet_sim workload, benchgen renders the committed
    BENCH_fleet_sim.json artifact, and the artifact records >=2,000
    virtual nodes, >=10^5 tasks, every policy bundle on >=3 scenarios
    (including the preemption-wave chaos scenario) with exact
    partitions throughout and per-policy deltas vs baseline."""
    import json

    from batch_shipyard_tpu.sched import policy as sched_policy
    bench_src = (PACKAGE.parent / "bench.py").read_text(
        encoding="utf-8")
    assert '"fleet_sim" in workloads' in bench_src
    benchgen_src = (PACKAGE.parent / "tools" / "benchgen.py"
                    ).read_text(encoding="utf-8")
    assert "BENCH_fleet_sim.json" in benchgen_src
    artifact = PACKAGE.parent / "BENCH_fleet_sim.json"
    assert artifact.exists(), (
        "BENCH_fleet_sim.json not committed — run "
        "`python bench.py --workloads fleet_sim`")
    data = json.loads(artifact.read_text(
        encoding="utf-8"))["fleet_sim"]
    assert data["nodes"] >= 2000
    assert data["tasks"] >= 100_000
    assert data["all_partitions_exact"] is True
    assert data.get("cpu_marker") is True
    assert set(data["policies"]) == set(sched_policy.POLICIES)
    assert len(data["scenarios"]) >= 3
    assert "preemption_wave" in data["scenarios"]
    for scenario, section in data["scenarios"].items():
        assert set(section) == set(sched_policy.POLICIES), scenario
        for policy, row in section.items():
            assert row["partition_exact"] is True, (scenario, policy)
            assert row["fingerprint"]
            if policy != "baseline":
                assert "goodput_ratio_delta" in \
                    row["delta_vs_baseline"], (scenario, policy)


def test_serving_slo_dispatched_and_rendered():
    """The prefix-cache/SLO proof is wired end to end: bench.py
    dispatches the serving_slo workload, benchgen renders the
    committed BENCH_serving_slo.json, and the artifact clears the
    acceptance gates — prefix hit rate > 0.5, prefix-cache-on mean
    AND p99 TTFT strictly below the cache-off control at the same
    seed, and byte-identical greedy outputs between the two arms."""
    import json

    bench_src = (PACKAGE.parent / "bench.py").read_text(
        encoding="utf-8")
    assert '"serving_slo" in workloads' in bench_src
    benchgen_src = (PACKAGE.parent / "tools" / "benchgen.py"
                    ).read_text(encoding="utf-8")
    assert "BENCH_serving_slo.json" in benchgen_src
    artifact = PACKAGE.parent / "BENCH_serving_slo.json"
    assert artifact.exists(), (
        "BENCH_serving_slo.json not committed — run "
        "`python bench.py --workloads serving_slo`")
    data = json.loads(artifact.read_text(
        encoding="utf-8"))["serving_slo"]
    assert data.get("cpu_marker") is True
    assert data["prefix_hit_rate"] > 0.5
    assert data["outputs_identical"] is True
    on, off = data["prefix_cache_on"], data["prefix_cache_off"]
    assert on["completed"] == off["completed"] == \
        data["num_requests"]
    assert on["ttft_mean_ms"] < off["ttft_mean_ms"]
    assert on["ttft_exact_ms"]["p99"] < off["ttft_exact_ms"]["p99"]
    assert on["outputs_sha256"] == off["outputs_sha256"]
    for arm in (on, off):
        assert set(arm["slo_attainment"]) == {
            "interactive", "standard", "batch"}


def test_chaos_kinds_help_lists_node_preempt_notice():
    """The --kinds help derives from INJECTION_KINDS (analyzer rule
    wiring-kinds-help-stale) and the rendered help really names the
    advance-notice kind."""
    from batch_shipyard_tpu.chaos.plan import INJECTION_KINDS
    assert "node_preempt_notice" in INJECTION_KINDS
    findings = _run("wiring-kinds-help-stale")
    assert not findings, _fail_lines(findings)
    import click

    from batch_shipyard_tpu.cli import main as cli_main
    ctx = click.Context(cli_main.chaos_plan, info_name="plan")
    # click wraps long help lines mid-token: collapse whitespace
    # before matching.
    rendered = "".join(cli_main.chaos_plan.get_help(ctx).split())
    assert "node_preempt_notice" in rendered


def test_scheduler_scale_workload_dispatched_and_rendered():
    """The 10^6 proof is wired end to end: bench.py dispatches the
    scheduler_scale workload, benchgen reads the committed
    BENCH_scheduler_scale.json artifact, and the artifact itself
    records a complete, partition-exact 10^6-task run whose submit
    leg (server-side expansion, streaming batched submission) is no
    longer the dominant cost."""
    import json
    bench_src = (PACKAGE.parent / "bench.py").read_text(
        encoding="utf-8")
    assert '"scheduler_scale" in workloads' in bench_src
    benchgen_src = (PACKAGE.parent / "tools" / "benchgen.py"
                    ).read_text(encoding="utf-8")
    assert "BENCH_scheduler_scale.json" in benchgen_src
    artifact = PACKAGE.parent / "BENCH_scheduler_scale.json"
    assert artifact.exists(), (
        "BENCH_scheduler_scale.json not committed — run "
        "`python bench.py --workloads scheduler_scale`")
    data = json.loads(artifact.read_text(
        encoding="utf-8"))["scheduler_scale"]
    assert data["num_tasks"] >= 1_000_000
    assert data["completed"] is True
    assert data["goodput"]["partition_exact"] is True
    assert data["server_side_expansion"] is True
    # Submission must not dominate: the materialization leg is
    # strictly cheaper than the drain, and >= 10x the pre-streaming
    # submitter's 1648 tasks/s.
    assert data["submit_seconds"] < data["run_seconds"]
    assert data["submit_tasks_per_second"] >= 16_480


def test_train_workloads_enable_the_compile_cache():
    findings = _run("wiring-compile-cache-optout")
    assert not findings, _fail_lines(findings)


def test_benchgen_phase_and_workload_names_exist():
    """Every silicon-proof phase name tools/benchgen.py binds to
    (p.get("phase") == "X") must be record()-ed by
    tools/silicon_proof.py, and every bench workload a silicon-proof
    phase command invokes (--workloads X) must be dispatched by
    bench.py ("X" in workloads) — a renamed phase cannot silently
    turn a docs section or a pipeline phase into a no-op."""
    tools = PACKAGE.parent / "tools"
    benchgen_tree = ast.parse(
        (tools / "benchgen.py").read_text(encoding="utf-8"))
    proof_src = (tools / "silicon_proof.py").read_text(
        encoding="utf-8")
    proof_tree = ast.parse(proof_src)
    bench_tree = ast.parse(
        (PACKAGE.parent / "bench.py").read_text(encoding="utf-8"))

    recorded = set()
    workloads_invoked = set()
    for node in ast.walk(proof_tree):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr == "record" and node.args and \
                isinstance(node.args[0], ast.Constant):
            recorded.add(node.args[0].value)
        # ["...", "--workloads", "X", ...] command lists.
        if isinstance(node, ast.List):
            values = [e.value for e in node.elts
                      if isinstance(e, ast.Constant) and
                      isinstance(e.value, str)]
            for i, value in enumerate(values[:-1]):
                if value == "--workloads":
                    workloads_invoked |= {
                        w.strip() for w in values[i + 1].split(",")}

    referenced = set()
    for node in ast.walk(benchgen_tree):
        # p.get("phase") == "X" comparisons.
        if isinstance(node, ast.Compare) and \
                isinstance(node.left, ast.Call) and \
                isinstance(node.left.func, ast.Attribute) and \
                node.left.func.attr == "get" and node.left.args and \
                isinstance(node.left.args[0], ast.Constant) and \
                node.left.args[0].value == "phase":
            for comparator in node.comparators:
                if isinstance(comparator, ast.Constant) and \
                        isinstance(comparator.value, str):
                    referenced.add(comparator.value)
    assert referenced, "no phase references found in benchgen.py"
    missing = referenced - recorded
    assert not missing, (
        f"benchgen.py binds to silicon-proof phases {sorted(missing)} "
        f"that tools/silicon_proof.py never records")

    dispatched = set()
    for node in ast.walk(bench_tree):
        # "X" in workloads dispatch checks.
        if isinstance(node, ast.Compare) and \
                isinstance(node.left, ast.Constant) and \
                isinstance(node.left.value, str) and \
                len(node.ops) == 1 and \
                isinstance(node.ops[0], ast.In) and \
                isinstance(node.comparators[0], ast.Name) and \
                node.comparators[0].id == "workloads":
            dispatched.add(node.left.value)
    assert dispatched, "no workload dispatch found in bench.py"
    missing = workloads_invoked - dispatched
    assert not missing, (
        f"silicon_proof.py invokes bench workloads {sorted(missing)} "
        f"that bench.py never dispatches")
    # The kernel phase is wired end to end.
    assert "ring_collectives" in recorded
    assert "ring_collectives" in dispatched


def test_span_kinds_are_declared_in_trace_spans():
    findings = _run("trace-span-undeclared")
    assert not findings, _fail_lines(findings)
    # The span log's table rides the names registry like every other
    # coordination surface.
    assert names.TABLE_TRACE == "trace"


def test_trace_and_profile_fleet_actions_are_wired_in_cli():
    """Widened by the analyzer: EVERY fleet action_* needs a
    cli/main.py call site now, not just the trace/profile family."""
    findings = _run("wiring-cli-action-unwired")
    assert not findings, _fail_lines(findings)


def test_train_loops_never_call_blocking_checkpoint_save():
    findings = _run("jax-blocking-save-in-train")
    assert not findings, _fail_lines(findings)

"""Static consistency — now a thin wrapper over `shipyard lint`.

The table/event/span/state/CLI-action AST scans that used to live
here are registered analyzer rules (batch_shipyard_tpu/analysis/,
PR 11); each historical test keeps its name and coverage but runs
the corresponding rule over the real tree, so tier-1 sees the same
gates while the CLI (`shipyard lint`) and tests/test_analysis.py
share one implementation. Checks with no analyzer analog (drill
and docs wiring, what PR 45 deleted staying deleted) stay native below.
"""

import ast
import fnmatch
import os
import pathlib
import re

import pytest

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


def test_train_workloads_enable_the_compile_cache():
    findings = _run("wiring-compile-cache-optout")
    assert not findings, _fail_lines(findings)


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


# ------------- a drill has a command; the docs name what exists -------------

# The one fakepod drill no flag selects: it injects no fault, and its
# two callers are tests/test_preemption.py's scale tests.
_NO_FLAG_DRILL = "drill.run_scheduler_scale_drill"


def _drill_functions() -> list[str]:
    """``module.function`` of every top-level ``run_*drill`` in
    chaos/drill.py and chaos/serving_drill.py, read from the source
    so collecting this file imports no model code."""
    found = []
    for module in ("drill", "serving_drill"):
        tree = ast.parse((PACKAGE / "chaos" / f"{module}.py").read_text(
            encoding="utf-8"))
        found += [f"{module}.{node.name}" for node in tree.body
                  if isinstance(node, ast.FunctionDef)
                  and re.fullmatch(r"run_(\w+_)?drill", node.name)]
    return found


@pytest.fixture(scope="module")
def drills_run_by_flag():
    """`shipyard chaos drill` run once without a drill flag and once
    with each on/off option of the click command, every drill function
    replaced by a recorder: {flag or None: [module.function, ...]}."""
    import importlib

    from click.testing import CliRunner

    from batch_shipyard_tpu.cli import main as cli_main
    calls: list[str] = []
    report = {"seed": 0, "fingerprint": "", "invariants": {},
              "applied": []}
    flags = [None] + [param.opts[0] for param in cli_main.chaos_drill.params
                      if getattr(param, "is_flag", False)]
    ran = {}
    with pytest.MonkeyPatch.context() as patch:
        for name in _drill_functions():
            module, function = name.split(".")
            patch.setattr(
                importlib.import_module(
                    f"batch_shipyard_tpu.chaos.{module}"), function,
                lambda *args, _name=name, **kwargs:
                    calls.append(_name) or report)
        for flag in flags:
            calls.clear()
            result = CliRunner().invoke(
                cli_main.cli, ["chaos", "drill"] + ([flag] if flag else []))
            assert result.exit_code == 0, (flag, result.output)
            ran[flag] = list(calls)
    return ran


@pytest.mark.parametrize(
    "drill", [name for name in _drill_functions()
              if name != _NO_FLAG_DRILL])
def test_every_drill_is_reachable_from_the_cli(drill, drills_run_by_flag):
    """A drill cannot become test-only: `shipyard chaos drill`, with
    one of its flags or with none, hands the seed to
    fleet.action_chaos_drill, which runs exactly this function."""
    selects = [flag for flag, ran in drills_run_by_flag.items()
               if ran == [drill]]
    assert selects, (
        f"no flag of `shipyard chaos drill` runs chaos/{drill} alone: "
        f"{drills_run_by_flag}")


def _nav_pages(nav) -> list[str]:
    if isinstance(nav, str):
        return [nav]
    if isinstance(nav, dict):
        nav = list(nav.values())
    return [page for entry in nav for page in _nav_pages(entry)]


def test_docs_nav_names_only_pages_that_exist():
    import yaml
    site = yaml.safe_load((PACKAGE.parent / "mkdocs.yml").read_text(
        encoding="utf-8"))
    pages = _nav_pages(site["nav"])
    assert pages
    missing = [page for page in pages
               if not (PACKAGE.parent / "docs" / page).is_file()]
    assert not missing, f"mkdocs.yml's nav names no file: {missing}"


# What PR 45 deleted: the pre-chip measurement layer. The benchmark of
# record is benchmark/run.py, BENCHMARK.json, PERF_LEDGER.jsonl, PERF.md.
_DELETED_LAYER = re.compile(
    r"(?<![A-Za-z0-9_])bench\.py|benchgen|silicon_proof"
    r"|SHIPYARD_XLA_TUNING|26-benchmarks|BENCH_DETAILS|BENCH_GANTT"
    r"|COMPILE_WARM_DETAILS|BENCH_[a-z_]+\.json")
# The driver's and the seed's records, and the documents whose job is
# to say what happened: they may name what is gone.
_HISTORY = ("CHANGES.md", "PERF.md", "ROADMAP.md", "ISSUE.md", "REVIEW.md",
            "PERF_LEDGER.jsonl", "VERDICT.md", "ADVICE.md", "SURVEY.md",
            "PAPER*.md", "BASELINE.*", "BENCH_r[0-9]*.json",
            "MULTICHIP_r[0-9]*.json", "tests/test_names_consistency.py")


def _committed_files(root: pathlib.Path):
    """The files git would commit, without asking git (the driver's
    copy of the tree has no .git): a walk that leaves out `.git` and
    whatever a line of the root's .gitignore names."""
    ignored = [pattern.rstrip("/") for pattern in
               (root / ".gitignore").read_text(encoding="utf-8").split()]

    def skipped(name: str) -> bool:
        return name == ".git" or any(
            fnmatch.fnmatch(name, pattern) for pattern in ignored)

    for folder, dirs, files in os.walk(root):
        dirs[:] = [name for name in dirs if not skipped(name)]
        for name in files:
            if not skipped(name):
                yield pathlib.Path(folder, name)


def test_no_tracked_file_names_the_deleted_layer():
    root = PACKAGE.parent
    naming = []
    for path in _committed_files(root):
        relative = path.relative_to(root).as_posix()
        if any(fnmatch.fnmatch(relative, pattern) for pattern in _HISTORY):
            continue
        hit = _DELETED_LAYER.search(
            path.read_text(encoding="utf-8", errors="ignore"))
        if hit:
            naming.append(f"{relative}: {hit.group(0)}")
    assert not naming, "\n".join(naming)

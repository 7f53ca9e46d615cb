"""Hot-loop rules: work that runs once per heartbeat must stay cheap.

The node agent's heartbeat thread drives every sweep
(agent/node_agent.py _heartbeat_loop): retention, orphaned-gang
janitor, preemption sweep, request forwarding. Anything slow or
store-heavy inside that path multiplies by pool size and by heartbeat
rate — the PR 10 review settled the discipline: unpartitioned table
scans are allowed only behind a leader gate (today the lease-backed
_sweep_leader_epoch; historically _is_gang_sweep_leader), so a pool
pays ONE scan per interval, not one per node; and a sweep must never
sleep (a blocked sweep starves the heartbeat itself, and a
heartbeat-stale node gets its running tasks reclaimed as orphans).
Since PR 13 the gate must be a NAMED LEASE with a fencing epoch
(leader-sweep-no-lease): heartbeat-freshness elections have a
double-leader window that fences nothing.
"""

from __future__ import annotations

import ast
import re

from batch_shipyard_tpu.analysis.core import (
    AnalysisContext, Finding, call_name, keyword_arg, rule)

# Functions that run on the heartbeat cadence: the sweep/heartbeat
# naming convention is load-bearing (the existing sweeps all follow
# it), so the rule keys on it.
_HOT_NAME_RE = re.compile(r"(^|_)(sweep|heartbeat)(_|$)")


def _is_hot(fn: ast.FunctionDef) -> bool:
    return bool(_HOT_NAME_RE.search(fn.name))


def _leader_gated(fn: ast.FunctionDef) -> bool:
    """A call to a leadership helper anywhere in the function body
    (the _sweep_leader_epoch idiom; the deleted
    _is_gang_sweep_leader also matched) marks the whole function as
    one-scan-per-pool."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            name = call_name(node)
            if name and "leader" in name:
                return True
    return False


@rule("loop-unpartitioned-scan", family="loop")
def check_unpartitioned_scan(ctx: AnalysisContext) -> list[Finding]:
    """``query_entities`` with no partition key inside a
    heartbeat/sweep function that is not leader-gated: every node in
    the pool pays a full-table scan per heartbeat, so store load
    scales as nodes x rows x rate.

    Provenance: the PR 5 orphaned-gang janitor originally scanned
    the gang table from EVERY node each heartbeat; the PR 10 review
    leader-gated it (one unpartitioned scan per pool per interval)
    and the preemption sweep was born gated. New sweeps must follow
    the precedent or partition the scan."""
    findings = []
    for src in ctx.python_files:
        for fn in [n for n in ast.walk(src.tree)
                   if isinstance(n, ast.FunctionDef)]:
            if not _is_hot(fn) or _leader_gated(fn):
                continue
            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call) and
                        call_name(node) == "query_entities"):
                    continue
                pk = (keyword_arg(node, "partition_key")
                      or (node.args[1] if len(node.args) > 1
                          else None))
                unpartitioned = pk is None or (
                    isinstance(pk, ast.Constant) and pk.value is None)
                if unpartitioned:
                    findings.append(Finding(
                        rule="loop-unpartitioned-scan", path=src.rel,
                        line=node.lineno,
                        message=(f"unpartitioned query_entities scan "
                                 f"in heartbeat-cadence function "
                                 f"{fn.name!r} without a leader "
                                 f"gate; every node pays it every "
                                 f"interval")))
    return findings


@rule("leader-sweep-no-lease", family="loop")
def check_leader_sweep_no_lease(ctx: AnalysisContext
                                ) -> list[Finding]:
    """A sweep-cadence function that performs unpartitioned scans or
    stamps cross-node decisions (``request_preemption``) must hold a
    NAMED LEASE with a fencing epoch — a call whose name carries the
    ``leader_epoch`` / ``sweep_lease`` idiom (state/leases.py) — and
    any ``request_preemption`` it fires must thread the epoch
    through (a ``leader_epoch=`` keyword). A heartbeat-freshness
    election is not a lease: it cannot fence a deposed leader's
    in-flight writes.

    Provenance: the PR 12 gang janitor shipped with "a brief
    double-leader window during failover is harmless because
    clearing is idempotent" — true for the janitor, already false
    for the preempt sweep sharing the same election, whose stamps
    elect victims (two leaders, two victims for one starved task).
    PR 13 deleted that comment by deleting the window: the election
    became a store lease whose holder abdicates on its own clock
    strictly before a successor can acquire, fenced by a monotonic
    term epoch. This rule keeps the next sweep from re-growing the
    window."""
    findings = []
    for src in ctx.python_files:
        for fn in [n for n in ast.walk(src.tree)
                   if isinstance(n, ast.FunctionDef)]:
            if not _is_hot(fn):
                continue
            calls = [n for n in ast.walk(fn)
                     if isinstance(n, ast.Call)]
            names_called = {call_name(n) for n in calls}
            names_called.discard(None)
            unpartitioned = False
            for node in calls:
                if call_name(node) != "query_entities":
                    continue
                pk = (keyword_arg(node, "partition_key")
                      or (node.args[1] if len(node.args) > 1
                          else None))
                if pk is None or (isinstance(pk, ast.Constant)
                                  and pk.value is None):
                    unpartitioned = True
            stamps = "request_preemption" in names_called
            if not unpartitioned and not stamps:
                continue
            leased = any(("leader_epoch" in name
                          or "sweep_lease" in name)
                         for name in names_called)
            if not leased:
                findings.append(Finding(
                    rule="leader-sweep-no-lease", path=src.rel,
                    line=fn.lineno,
                    message=(f"sweep {fn.name!r} performs "
                             f"{'unpartitioned scans' if unpartitioned else 'cross-node stamps'} "
                             f"without holding a named lease (no "
                             f"leader_epoch/sweep_lease call) — a "
                             f"heartbeat-freshness election has a "
                             f"double-leader window and no fencing")))
                continue
            for node in calls:
                if call_name(node) == "request_preemption" and \
                        keyword_arg(node, "leader_epoch") is None:
                    findings.append(Finding(
                        rule="leader-sweep-no-lease", path=src.rel,
                        line=node.lineno,
                        message=(f"request_preemption in sweep "
                                 f"{fn.name!r} does not thread the "
                                 f"lease epoch through "
                                 f"(leader_epoch=...) — a deposed "
                                 f"leader's stamp would be "
                                 f"indistinguishable from the "
                                 f"successor's")))
    return findings


@rule("preempt-grace-unbounded", family="loop")
def check_preempt_grace_unbounded(ctx: AnalysisContext
                                  ) -> list[Finding]:
    """A sweep that stamps preemption notices
    (``request_preemption``) must have a reachable ESCALATION path
    in the same function — a call whose name mentions escalate or
    evict. Without one, a victim that ignores its notice squats on
    the slot forever: the notice is a request, and a request with no
    enforcement ladder is an unbounded grace window.

    Provenance: the PR 10 -> PR 12 gap this rule's PR fixes —
    cooperative-only preemption shipped a sweep that stamped notices
    with NO escalation rung, documented only as an honesty paragraph
    in docs/19; the forcible-eviction drill exists because nothing
    structural kept the next sweep from repeating the shape. Scoped
    to sweep/heartbeat-cadence functions: a manual CLI preempt and
    the chaos injectors carry their own follow-through."""
    findings = []
    for src in ctx.python_files:
        for fn in [n for n in ast.walk(src.tree)
                   if isinstance(n, ast.FunctionDef)]:
            if not _is_hot(fn):
                continue
            calls = {call_name(node)
                     for node in ast.walk(fn)
                     if isinstance(node, ast.Call)}
            calls.discard(None)
            if "request_preemption" not in calls:
                continue
            if any("escalat" in name or "evict" in name
                   for name in calls):
                continue
            findings.append(Finding(
                rule="preempt-grace-unbounded", path=src.rel,
                line=fn.lineno,
                message=(f"sweep {fn.name!r} stamps preemption "
                         f"notices but has no reachable escalation "
                         f"path (no escalate/evict call) — a victim "
                         f"that ignores its notice is never "
                         f"evicted")))
    return findings


@rule("loop-sleep-in-sweep", family="loop")
def check_sleep_in_sweep(ctx: AnalysisContext) -> list[Finding]:
    """``time.sleep`` inside a heartbeat/sweep function: the sweep
    runs ON the heartbeat thread, so sleeping there delays the
    node's own liveness signal — long enough, and the orphan-reclaim
    path judges the node dead and steals its running tasks.

    Provenance: the alive-but-stuck hang class — the progress
    watchdog exists because blocked control loops turn into
    silently-dead nodes. Waiting belongs in the poll loops (which
    sleep poll_interval between EMPTY polls), never in sweep
    bodies; a sweep that needs to wait should record state and
    finish next interval."""
    findings = []
    for src in ctx.python_files:
        for fn in [n for n in ast.walk(src.tree)
                   if isinstance(n, ast.FunctionDef)]:
            if not _is_hot(fn):
                continue
            # The loop driver itself (e.g. _heartbeat_loop) paces on
            # stop_event.wait — a plain while-loop wrapper is exempt
            # only for that idiom, so time.sleep still flags.
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and \
                        call_name(node) == "sleep" and \
                        isinstance(node.func, ast.Attribute) and \
                        isinstance(node.func.value, ast.Name) and \
                        node.func.value.id == "time":
                    findings.append(Finding(
                        rule="loop-sleep-in-sweep", path=src.rel,
                        line=node.lineno,
                        message=(f"time.sleep inside "
                                 f"heartbeat-cadence function "
                                 f"{fn.name!r} stalls the heartbeat "
                                 f"thread; pace on stop_event.wait "
                                 f"or defer to the next interval")))
    return findings

"""Env-contract rules: the $SHIPYARD_* surface is a typed interface.

The task env contract (agent/task_runner.py module docstring) is how
every workload talks to the scheduler: goodput sinks, progress beats,
preempt requests, trace context, compile-cache dirs. It has three
legs that must agree:

  1. every variable a workload READS must be exported by the agent
     (or be a declared operator knob),
  2. every variable the agent EXPORTS must have a reader or be part
     of the documented task contract,
  3. every variable set by build_task_env must survive the docker
     boundary (docker run starts from an empty env: anything not
     forwarded with -e silently vanishes inside the container).

Before this PR the ~25-variable contract was maintained by hand —
and leg 3 had already drifted: SHIPYARD_TASK_DIR and
SHIPYARD_TASK_SLOT were set for subprocess tasks but missing from
the docker forward list (fixed in this PR).
"""

from __future__ import annotations

import ast
import re
from typing import Optional

from batch_shipyard_tpu.analysis.core import (
    AnalysisContext, Finding, call_name, const_str, rule)

_VAR_RE = re.compile(r"SHIPYARD_[A-Z0-9_]+")

# Operator/process-level knobs: read from the OPERATOR's environment
# (CLI, tools, kernel selection), never part of the task env the
# agent synthesizes — so "read but not exported" is their correct
# steady state. Adding a var here is a reviewed statement that it is
# operator surface, not task contract.
OPERATOR_ENV_VARS = frozenset({
    "SHIPYARD_CONFIGDIR",           # cli/main.py --configdir envvar
    "SHIPYARD_SECRETS_FILE",        # agent bootstrap secret source
    "SHIPYARD_FORCE_TPU_PASSTHROUGH",  # docker device passthrough
})

_ENVISH_NAME_RE = re.compile(r"(^env$|_env$|^environ$|^env_)")


def _envish(node: ast.expr) -> bool:
    """Heuristic: is this expression an environment mapping? Matches
    os.environ and the agent's env/jp_env/jr_env dict idioms."""
    if isinstance(node, ast.Attribute):
        return node.attr == "environ"
    if isinstance(node, ast.Name):
        return bool(_ENVISH_NAME_RE.search(node.id))
    return False


def _env_const_table(ctx: AnalysisContext) -> dict[str, str]:
    """Bare-name -> value for every module-level *_ENV = "SHIPYARD_*"
    constant in the package (GOODPUT_FILE_ENV, TRACE_FILE_ENV, ...),
    so exports written through constants resolve."""
    table: dict[str, str] = {}
    for src in ctx.python_files:
        for node in ast.iter_child_nodes(src.tree):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Constant) and \
                    isinstance(node.value.value, str) and \
                    node.value.value.startswith("SHIPYARD_"):
                for target in node.targets:
                    if isinstance(target, ast.Name):
                        table[target.id] = node.value.value
    return table


def _resolve_var(node: Optional[ast.expr],
                 consts: dict[str, str]) -> Optional[str]:
    if node is None:
        return None
    value = const_str(node)
    if value is not None:
        return value if value.startswith("SHIPYARD_") else None
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    if isinstance(node, ast.Attribute):
        return consts.get(node.attr)
    return None


def _collect_reads(ctx: AnalysisContext, consts: dict[str, str],
                   ) -> dict[str, tuple[str, int]]:
    """var -> first (path, line) that reads it via os.environ.get /
    os.getenv / os.environ[...] / env.get(...)."""
    reads: dict[str, tuple[str, int]] = {}

    def note(var, src, line):
        if var:
            reads.setdefault(var, (src.rel, line))

    for src in ctx.python_files:
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call):
                name = call_name(node)
                if name == "getenv" and node.args:
                    note(_resolve_var(node.args[0], consts), src,
                         node.lineno)
                elif name == "get" and node.args and \
                        isinstance(node.func, ast.Attribute) and \
                        _envish(node.func.value):
                    note(_resolve_var(node.args[0], consts), src,
                         node.lineno)
            elif isinstance(node, ast.Subscript) and \
                    isinstance(node.ctx, ast.Load) and \
                    _envish(node.value):
                note(_resolve_var(node.slice, consts), src,
                     node.lineno)
    return reads


def _collect_exports(ctx: AnalysisContext, consts: dict[str, str],
                     ) -> dict[str, tuple[str, int]]:
    """var -> first (path, line) that exports it into a task/process
    env: env["X"]=..., env.setdefault(X,...), env.update({...}),
    and dict literals with SHIPYARD_* keys inside *env* functions
    (build_task_env, TraceContext.env, the jp_env/jr_env blocks)."""
    exports: dict[str, tuple[str, int]] = {}

    def note(var, src, line):
        if var:
            exports.setdefault(var, (src.rel, line))

    for src in ctx.python_files:
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Assign):
                for target in node.targets:
                    if isinstance(target, ast.Subscript) and \
                            _envish(target.value):
                        note(_resolve_var(target.slice, consts),
                             src, target.lineno)
                    # jp_env = {"SHIPYARD_X": ...} dict-literal
                    # exports.
                    if isinstance(target, ast.Name) and \
                            _envish(target) and \
                            isinstance(node.value, ast.Dict):
                        for key in node.value.keys:
                            note(_resolve_var(key, consts), src,
                                 node.lineno)
            elif isinstance(node, ast.Call):
                name = call_name(node)
                if name == "setdefault" and node.args and \
                        isinstance(node.func, ast.Attribute) and \
                        _envish(node.func.value):
                    note(_resolve_var(node.args[0], consts), src,
                         node.lineno)
                elif name == "update" and node.args and \
                        isinstance(node.func, ast.Attribute) and \
                        _envish(node.func.value) and \
                        isinstance(node.args[0], ast.Dict):
                    for key in node.args[0].keys:
                        note(_resolve_var(key, consts), src,
                             node.lineno)
        # Dict literals returned by env-building functions
        # (TraceContext.env, launcher env synthesis).
        for fn in [n for n in ast.walk(src.tree)
                   if isinstance(n, ast.FunctionDef)
                   and "env" in n.name]:
            for node in ast.walk(fn):
                if isinstance(node, ast.Dict):
                    for key in node.keys:
                        note(_resolve_var(key, consts), src,
                             node.lineno)
    return exports


def _documented_contract(ctx: AnalysisContext) -> frozenset:
    """Vars named in agent/task_runner.py's module docstring — the
    published task contract; exported-but-unread is legal for these
    (user task commands outside this repo are the readers)."""
    src = ctx.get("batch_shipyard_tpu/agent/task_runner.py")
    if src is None or not isinstance(src.tree, ast.Module):
        return frozenset()
    doc = ast.get_docstring(src.tree) or ""
    return frozenset(_VAR_RE.findall(doc))


@rule("env-read-unexported", family="env")
def check_read_unexported(ctx: AnalysisContext) -> list[Finding]:
    """A $SHIPYARD_* variable is read somewhere in the package but no
    agent code path ever exports it and it is not a declared operator
    knob (OPERATOR_ENV_VARS): the reader's branch is dead — it will
    see the default forever, silently.

    Provenance: the adaptive progress-beat throttle (PR 5 review)
    shipped reading $SHIPYARD_PROGRESS_DEADLINE before the agent
    export existed; only review caught that the throttle could starve
    a tight deadline. This rule makes the export a build error."""
    consts = _env_const_table(ctx)
    reads = _collect_reads(ctx, consts)
    exports = _collect_exports(ctx, consts)
    findings = []
    for var, (path, line) in sorted(reads.items()):
        if var in exports or var in OPERATOR_ENV_VARS:
            continue
        findings.append(Finding(
            rule="env-read-unexported", path=path, line=line,
            message=(f"${var} is read but never exported by "
                     f"node_agent/task_runner and is not a declared "
                     f"operator knob (rules_env.OPERATOR_ENV_VARS)")))
    return findings


@rule("env-export-unread", family="env")
def check_export_unread(ctx: AnalysisContext) -> list[Finding]:
    """A $SHIPYARD_* variable is exported into task envs but nothing
    in the package reads it and the task_runner docstring (the
    published contract user commands rely on) does not document it:
    dead surface, or — worse — a typo'd twin of the var the reader
    actually polls.

    Provenance: the 25+-variable contract audit this analyzer
    replaced; a renamed export with a stale reader is invisible to
    every runtime test because os.environ.get defaults paper over
    it."""
    consts = _env_const_table(ctx)
    reads = _collect_reads(ctx, consts)
    exports = _collect_exports(ctx, consts)
    documented = _documented_contract(ctx)
    findings = []
    for var, (path, line) in sorted(exports.items()):
        if var in reads or var in documented:
            continue
        findings.append(Finding(
            rule="env-export-unread", path=path, line=line,
            message=(f"${var} is exported but has no in-package "
                     f"reader and is not documented in the "
                     f"task_runner env contract")))
    return findings


@rule("env-docker-unmapped", family="env")
def check_docker_unmapped(ctx: AnalysisContext) -> list[Finding]:
    """A variable set by build_task_env (the core per-task identity
    contract) does not appear anywhere in synthesize_command's docker
    branch: `docker run` starts from an empty environment, so the
    variable exists for runtime=none tasks and silently vanishes for
    containerized ones — the contract forks by runtime.

    Provenance: found BY this rule in this PR — SHIPYARD_TASK_DIR
    and SHIPYARD_TASK_SLOT were missing from the docker forward
    list since the runner was written (fixed alongside)."""
    findings = []
    for src in ctx.python_files:
        build_fn = None
        synth_fn = None
        for fn in [n for n in ast.walk(src.tree)
                   if isinstance(n, ast.FunctionDef)]:
            if fn.name == "build_task_env":
                build_fn = fn
            elif fn.name == "synthesize_command":
                synth_fn = fn
        if build_fn is None or synth_fn is None:
            continue
        # Docker-visible vars: every SHIPYARD_* token inside the
        # function's STRING CONSTANTS (the -e lists, tuple
        # constants, and "-e VAR=value" remap f-string parts).
        # AST constants only, docstring excluded — a variable named
        # in a comment or in prose must not count as forwarded.
        doc_const = None
        if synth_fn.body and isinstance(synth_fn.body[0], ast.Expr) \
                and isinstance(synth_fn.body[0].value, ast.Constant):
            doc_const = synth_fn.body[0].value
        forwarded: set[str] = set()
        for node in ast.walk(synth_fn):
            if isinstance(node, ast.Constant) and \
                    node is not doc_const and \
                    isinstance(node.value, str):
                forwarded.update(_VAR_RE.findall(node.value))
        for node in ast.walk(build_fn):
            if not isinstance(node, ast.Dict):
                continue
            for key in node.keys:
                var = const_str(key)
                if var and var.startswith("SHIPYARD_") and \
                        var not in forwarded:
                    findings.append(Finding(
                        rule="env-docker-unmapped", path=src.rel,
                        line=key.lineno,
                        message=(f"${var} is set by build_task_env "
                                 f"but never forwarded across the "
                                 f"docker boundary in "
                                 f"synthesize_command (-e or remap)")))
    return findings

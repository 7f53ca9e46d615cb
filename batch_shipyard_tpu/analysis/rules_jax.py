"""JAX/determinism rules — enforced WITHOUT importing JAX.

Pure AST scans for the three accelerator bug classes this repo has
paid for: donated-buffer reuse (a runtime XLA error at best, silent
garbage at worst — the PR 6 opt-state sharding fix was adjacent to
exactly this), restoring over an undrained async checkpoint writer
(the PR 10 preemption drain contract), and wall-clock/global-random
calls inside functions whose whole value is determinism (chaos plans
named by seed+fingerprint, compile-cache identity keys that must
match across every node of a pool).
"""

from __future__ import annotations

import ast
from typing import Optional

from batch_shipyard_tpu.analysis.core import (
    AnalysisContext, Finding, call_name, keyword_arg, rule)

# Pure-by-contract functions: (file, function-name) pairs whose
# docstrings promise determinism — chaos plans are "a pure function
# of (seed, shape)" (chaos/plan.py) and cache identity "pure over
# explicit args" (compilecache/manager.py). Registering a function
# here is how a module opts its contract into machine enforcement.
PURE_CONTRACTS = {
    "batch_shipyard_tpu/chaos/plan.py":
        {"generate", "fingerprint", "to_dict", "from_dict", "param"},
    "batch_shipyard_tpu/compilecache/manager.py":
        {"_stable", "config_digest", "identity_key"},
}

# Calls that break determinism / purity. random.Random(seed) is fine
# (and is the chaos plan's whole mechanism); the MODULE-level
# random.random()/uniform()/... draws from hidden global state.
_IMPURE_TIME = {"time", "monotonic", "perf_counter", "time_ns"}
_IMPURE_RANDOM = {"random", "uniform", "randint", "randrange",
                  "choice", "shuffle", "sample", "getrandbits"}


def _impure_call(node: ast.Call) -> Optional[str]:
    func = node.func
    if not isinstance(func, ast.Attribute):
        return None
    base = func.value
    if not isinstance(base, ast.Name):
        return None
    if base.id == "time" and func.attr in _IMPURE_TIME:
        return f"time.{func.attr}"
    if base.id == "random" and func.attr in _IMPURE_RANDOM:
        return f"random.{func.attr}"
    if base.id == "datetime" and func.attr in ("now", "utcnow",
                                               "today"):
        return f"datetime.{func.attr}"
    if base.id == "uuid" and func.attr.startswith("uuid"):
        return f"uuid.{func.attr}"
    if base.id == "os" and func.attr == "urandom":
        return "os.urandom"
    if base.id == "secrets":
        return f"secrets.{func.attr}"
    return None


@rule("jax-impure-pure-fn", family="jax")
def check_impure_pure_fn(ctx: AnalysisContext) -> list[Finding]:
    """A wall-clock, global-random, or uuid call inside a registered
    pure-by-contract function (PURE_CONTRACTS): chaos plans must
    replay identically from a seed (operators name scenarios by
    fingerprint) and compile-cache identity keys must digest
    identically on every node (a drifting key re-compiles the whole
    pool and silently disables seeding).

    Provenance: the PR 4 cache-key review, where an
    address-carrying config field made two identical nodes disagree
    on identity until config_digest learned to scrub it — clock or
    RNG input is the same bug with worse odds."""
    findings = []
    for src in ctx.python_files:
        contract = PURE_CONTRACTS.get(src.rel)
        if not contract:
            continue
        for fn in [n for n in ast.walk(src.tree)
                   if isinstance(n, ast.FunctionDef)
                   and n.name in contract]:
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                impure = _impure_call(node)
                if impure:
                    findings.append(Finding(
                        rule="jax-impure-pure-fn", path=src.rel,
                        line=node.lineno,
                        message=(f"{impure}() inside pure-by-"
                                 f"contract function {fn.name!r}; "
                                 f"determinism is this function's "
                                 f"contract — thread the value in "
                                 f"as an argument")))
    return findings


def _donated_positions(node: ast.Call,
                       fn: Optional[ast.FunctionDef] = None
                       ) -> Optional[set[int]]:
    """Donated arg positions of a jax.jit(...) call expression, or
    None when it doesn't donate. ``donate_argnames`` (the spelling of
    models/serving.py's step programs) resolves against the
    positional parameters of ``fn``, the function being jitted, when
    the module defines it."""
    names = keyword_arg(node, "donate_argnames")
    if names is not None and fn is not None:
        params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        wanted = ([names] if isinstance(names, ast.Constant)
                  else getattr(names, "elts", []))
        out = {params.index(elt.value) for elt in wanted
               if isinstance(elt, ast.Constant)
               and elt.value in params}
        if out:
            return out
    donate = keyword_arg(node, "donate_argnums")
    if donate is None:
        return None
    if isinstance(donate, ast.Constant) and \
            isinstance(donate.value, int):
        return {donate.value}
    if isinstance(donate, (ast.Tuple, ast.List)):
        out = set()
        for elt in donate.elts:
            if isinstance(elt, ast.Constant) and \
                    isinstance(elt.value, int):
                out.add(elt.value)
        return out
    return set()


def _collect_donating_jits(tree: ast.AST) -> dict[str, set[int]]:
    """name -> donated positions, for both idioms:
    step = jax.jit(fn, donate_argnums=(0,)) assignments and
    @partial(jax.jit, donate_argnums=(0,)) decorators, with
    donate_argnames=("cache",) in either."""
    donating: dict[str, set[int]] = {}
    defs = {node.name: node for node in ast.walk(tree)
            if isinstance(node, ast.FunctionDef)}

    def jit_call(call: ast.Call) -> Optional[ast.Call]:
        name = call_name(call)
        if name == "jit":
            return call
        if name == "partial" and call.args:
            inner = call.args[0]
            if isinstance(inner, (ast.Attribute, ast.Name)) and \
                    (getattr(inner, "attr", None) == "jit"
                     or getattr(inner, "id", None) == "jit"):
                return call
        return None

    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, ast.Call):
            call = jit_call(node.value)
            if call is not None:
                jitted = call.args[0] if call.args else None
                positions = _donated_positions(
                    call, defs.get(getattr(jitted, "id", None)))
                if positions:
                    for target in node.targets:
                        if isinstance(target, ast.Name):
                            donating[target.id] = positions
        elif isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    call = jit_call(dec)
                    if call is not None:
                        positions = _donated_positions(call, node)
                        if positions:
                            donating[node.name] = positions
    return donating


def _own_statements(fn: ast.FunctionDef) -> list[ast.stmt]:
    """The function's statements in execution order, WITHOUT
    descending into nested function/class definitions (their bodies
    are separate scopes and separate simulations)."""
    out: list[ast.stmt] = []

    def visit(body: list[ast.stmt]) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef,
                                 ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            out.append(stmt)
            for field in ("body", "orelse", "finalbody"):
                child = getattr(stmt, field, None)
                if child:
                    visit(child)
            for handler in getattr(stmt, "handlers", []) or []:
                visit(handler.body)
    visit(fn.body)
    return out


@rule("jax-donated-reuse", family="jax")
def check_donated_reuse(ctx: AnalysisContext) -> list[Finding]:
    """A variable passed at a donated position of a jit'd function
    (donate_argnums, or donate_argnames resolved against the jitted
    function's signature) is read again in a LATER statement before
    being rebound: donation
    hands the buffer to XLA, so the old reference is garbage — a
    runtime error when you're lucky, silently corrupt numerics when
    you're not.

    Provenance: the PR 6 train-step review (donated opt-state
    aliased to a differently-sharded output was a runtime XLA error
    under tp); the blessed shape rebinds in one statement:
    ``params, opt = step(params, opt, batch)``."""
    findings = []
    for src in ctx.python_files:
        donating = _collect_donating_jits(src.tree)
        if not donating:
            continue
        for fn in [n for n in ast.walk(src.tree)
                   if isinstance(n, ast.FunctionDef)]:
            # donated name -> line it was consumed at. Statement
            # granularity: a statement's own loads are checked
            # against PRIOR donations only (the donating call's own
            # arguments are legitimate last uses), then its donations
            # register, then its stores rebind.
            consumed: dict[str, int] = {}
            for stmt in _own_statements(fn):
                donates: list[tuple[str, int]] = []
                loads: list[tuple[str, int]] = []
                stores: list[str] = []
                for node in ast.walk(stmt):
                    if isinstance(node, ast.Call):
                        fname = (node.func.id
                                 if isinstance(node.func, ast.Name)
                                 else None)
                        if fname in donating:
                            for pos in donating[fname]:
                                if pos < len(node.args) and \
                                        isinstance(node.args[pos],
                                                   ast.Name):
                                    donates.append(
                                        (node.args[pos].id,
                                         node.lineno))
                    elif isinstance(node, ast.Name):
                        if isinstance(node.ctx, ast.Load):
                            loads.append((node.id, node.lineno))
                        else:
                            stores.append(node.id)
                for name, line in loads:
                    if name in consumed:
                        findings.append(Finding(
                            rule="jax-donated-reuse", path=src.rel,
                            line=line,
                            message=(f"{name!r} was donated to a "
                                     f"jit'd call on line "
                                     f"{consumed[name]} and is read "
                                     f"again before being rebound; "
                                     f"the buffer no longer "
                                     f"exists")))
                        del consumed[name]
                for name, line in donates:
                    consumed.setdefault(name, line)
                for name in stores:
                    consumed.pop(name, None)
    return findings


@rule("jax-restore-no-drain", family="jax")
def check_restore_no_drain(ctx: AnalysisContext) -> list[Finding]:
    """A blocking ``restore(...)`` call in a module that uses
    AsyncCheckpointManager, with no ``wait_until_finished`` earlier
    in the function and no manager-presence guard around it: an
    in-flight async persist can still be writing the very directory
    the restore reads — torn reads of a checkpoint that was COMMITTED
    from the writer's point of view a moment later.

    Provenance: the PR 10 preempt drain contract (async writer
    drained BEFORE exit/restore); AsyncCheckpointManager.restore
    drains internally, which is the blessed shape."""
    findings = []
    for src in ctx.python_files:
        uses_async = any(
            (isinstance(node, (ast.Name, ast.Attribute)) and
             (getattr(node, "id", None) == "AsyncCheckpointManager"
              or getattr(node, "attr", None)
              == "AsyncCheckpointManager"))
            or (isinstance(node, ast.alias) and
                node.name == "AsyncCheckpointManager")
            for node in ast.walk(src.tree))
        if not uses_async:
            continue
        for fn in [n for n in ast.walk(src.tree)
                   if isinstance(n, ast.FunctionDef)]:
            # Only functions with an async manager in scope are at
            # risk: a legacy params-only loader that never touches a
            # manager has no writer to drain.
            if "manager" not in ast.dump(fn).lower():
                continue
            drained_lines = [
                node.lineno for node in ast.walk(fn)
                if isinstance(node, ast.Call)
                and call_name(node) == "wait_until_finished"]
            # Map call -> enclosing If tests (a `self.manager is
            # None`-style guard legitimizes the blocking branch).
            def guarded(call: ast.Call) -> bool:
                for node in ast.walk(fn):
                    if isinstance(node, ast.If) and \
                            "manager" in ast.dump(node.test):
                        span = (node.lineno,
                                getattr(node, "end_lineno",
                                        node.lineno))
                        if span[0] <= call.lineno <= span[1]:
                            return True
                return False

            for node in ast.walk(fn):
                if not (isinstance(node, ast.Call)
                        and call_name(node) == "restore"):
                    continue
                # manager.restore drains internally — only the
                # module-level blocking loader is at risk.
                if isinstance(node.func, ast.Attribute) and \
                        isinstance(node.func.value, ast.Attribute) \
                        and node.func.value.attr == "manager":
                    continue
                if any(line < node.lineno for line in drained_lines):
                    continue
                if guarded(node):
                    continue
                findings.append(Finding(
                    rule="jax-restore-no-drain", path=src.rel,
                    line=node.lineno,
                    message=("blocking restore() in an async-"
                             "checkpoint module without draining "
                             "the writer first; call "
                             "wait_until_finished() or guard on "
                             "the manager's absence")))
    return findings


@rule("jax-blocking-save-in-train", family="jax")
def check_blocking_save_in_train(ctx: AnalysisContext,
                                 ) -> list[Finding]:
    """A direct blocking ``checkpoint.save()`` in a train workload
    reintroduces the full-persist step stall the zero-stall pipeline
    (PR 3) exists to remove, and skips the stale-step guard — drive
    checkpoints through checkpoint.TrainCheckpointer.

    Provenance: the duplicate-final-save bug in train_transformer
    (PR 3), migrated from test_names_consistency."""
    findings = []
    for src in ctx.python_files:
        if not (src.rel.startswith("batch_shipyard_tpu/workloads/"
                                   "train_")
                and src.rel.endswith(".py")):
            continue
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "save" and \
                    isinstance(node.func.value, ast.Name) and \
                    node.func.value.id == "checkpoint":
                findings.append(Finding(
                    rule="jax-blocking-save-in-train", path=src.rel,
                    line=node.lineno,
                    message=("direct blocking checkpoint.save() in "
                             "a train workload; use "
                             "checkpoint.TrainCheckpointer")))
    return findings

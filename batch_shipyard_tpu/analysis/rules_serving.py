"""Serving-engine invariant rules.

The paged KV pool (models/kv_pages.py) runs a page lifecycle —
FREE -> OWNED -> PINNED (prefix-indexed, refcounted) -> LRU -> FREE —
whose accounting invariant (`_avail_pages` = total - pinned -
reservations) every admission decision trusts. `_release_pages` is
the only place a page may legally return to the free list, called by
the pool's release() and clear_unreferenced() once they have settled
the refcount, the LRU membership, and the availability counter. A
direct `_free_pages` mutation anywhere else, the engine included,
frees a page without that settlement: the page can be handed to a new
request while a shared prefix still references it — silent KV
corruption that decodes plausible-but-wrong tokens.
"""

from __future__ import annotations

import ast

from batch_shipyard_tpu.analysis.core import (
    AnalysisContext, Finding, rule)

# The only functions allowed to touch the free list directly, all
# PagePool's: construction seeds it, the allocator pops from it, and
# the release helper returns pages its callers have settled.
_ALLOWED_FUNCS = {"__init__", "_alloc_page", "_release_pages"}

# list-mutating method calls on the attribute.
_MUTATING_METHODS = {"append", "extend", "insert", "remove", "pop",
                     "clear", "sort", "reverse"}

_ATTR = "_free_pages"


def _is_free_pages_attr(node: ast.AST) -> bool:
    return isinstance(node, ast.Attribute) and node.attr == _ATTR


def _mutation(node: ast.AST) -> bool:
    """True when ``node`` mutates a ``*._free_pages`` attribute:
    a mutating method call, a (re)assignment or item assignment, an
    augmented assignment, or a del."""
    if isinstance(node, ast.Call) and \
            isinstance(node.func, ast.Attribute) and \
            node.func.attr in _MUTATING_METHODS and \
            _is_free_pages_attr(node.func.value):
        return True
    if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        for target in targets:
            if _is_free_pages_attr(target):
                return True
            if isinstance(target, ast.Subscript) and \
                    _is_free_pages_attr(target.value):
                return True
    if isinstance(node, ast.Delete):
        for target in node.targets:
            if _is_free_pages_attr(target) or (
                    isinstance(target, ast.Subscript) and
                    _is_free_pages_attr(target.value)):
                return True
    return False


def _walk_functions(tree: ast.AST):
    """Yield (enclosing_function_name, node) for every node, where
    the name is the innermost def/async def ('' at module level)."""

    def visit(node, func_name):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                yield from visit(child, child.name)
            else:
                yield func_name, child
                yield from visit(child, func_name)

    yield from visit(tree, "")


@rule("serving-page-refcount", family="serving")
def check_serving_page_refcount(ctx: AnalysisContext) -> list[Finding]:
    """A direct mutation of ``*._free_pages`` (append/extend/pop/
    assignment/del/...) outside ``__init__``/``_alloc_page``/
    ``_release_pages``: freeing or reassigning KV pool pages must go
    through PagePool.release / clear_unreferenced, which settle the
    prefix refcount, LRU membership, and the ``_avail_pages``
    accounting before the single release helper extends the list.
    A bare free-list write skips that settlement, so a page still
    referenced by a cached prefix can be reissued to a new request —
    the decode then gathers another request's KV rows and emits
    plausible-but-wrong tokens with no crash to flag it.

    Provenance: the first draft of slot teardown returned pages with
    ``self._free_pages.extend(self._slot_pages[i])`` directly — exactly
    right before prefix sharing existed, silently corrupting once a
    page could be pinned by the prefix index with refcount > 0. The
    shared-prefix churn test (tests/test_prefix_cache.py) only catches
    the shapes it generates; this rule closes the class."""
    findings = []
    for src in ctx.python_files:
        for func_name, node in _walk_functions(src.tree):
            if func_name in _ALLOWED_FUNCS:
                continue
            if _mutation(node):
                findings.append(Finding(
                    rule="serving-page-refcount", path=src.rel,
                    line=node.lineno,
                    message=(f"direct _free_pages mutation in "
                             f"{func_name or '<module>'}(); page "
                             f"frees must go through PagePool's "
                             f"release / _release_pages (they settle "
                             f"refcounts, LRU membership and "
                             f"_avail_pages — a bare free-list "
                             f"write can reissue a page a cached "
                             f"prefix still references)")))
    return findings


def _is_admission_call(node: ast.AST) -> bool:
    """A call that admits work into a ContinuousBatcher: the engine's
    slot-admission hook firing (``*.on_admit(...)``) or a front end
    enqueueing into the engine (``*.engine.submit(...)``)."""
    if not (isinstance(node, ast.Call) and
            isinstance(node.func, ast.Attribute)):
        return False
    if node.func.attr == "on_admit":
        return True
    return (node.func.attr == "submit" and
            isinstance(node.func.value, ast.Attribute) and
            node.func.value.attr == "engine")


@rule("serving-drain-no-admit", family="serving")
def check_serving_drain_no_admit(ctx: AnalysisContext
                                 ) -> list[Finding]:
    """A function that admits work into the ContinuousBatcher —
    firing the slot-admission hook (``on_admit``) or submitting into
    the engine (``*.engine.submit``) — without consulting the
    ``draining`` flag anywhere in its body. The drain ladder's whole
    guarantee is 'no admissions after the preempt/evict notice':
    every admission path must check ``draining`` before seating work,
    or a draining replica keeps accepting decodes the router already
    failed over — the same request then decodes on two replicas and
    the exactly-once stream contract breaks.

    Provenance: the drain feature landed with the check in
    ``_admit``; a later admission path (chunked-prefill fast path,
    a new batch front door) that forgets the flag would pass every
    drain test that doesn't exercise that specific path. This rule
    closes the class structurally."""
    findings = []
    for src in ctx.python_files:
        # Group nodes by enclosing function, then require any
        # admitting function to also reference ``draining``.
        by_func: dict[str, list[ast.AST]] = {}
        for func_name, node in _walk_functions(src.tree):
            by_func.setdefault(func_name, []).append(node)
        for func_name, nodes in by_func.items():
            admissions = [n for n in nodes if _is_admission_call(n)]
            if not admissions:
                continue
            checks_drain = any(
                (isinstance(n, ast.Attribute) and
                 n.attr == "draining") or
                (isinstance(n, ast.Name) and n.id == "draining")
                for n in nodes)
            if checks_drain:
                continue
            for call in admissions:
                findings.append(Finding(
                    rule="serving-drain-no-admit", path=src.rel,
                    line=call.lineno,
                    message=(f"{func_name or '<module>'}() admits "
                             f"into the ContinuousBatcher without "
                             f"checking the draining flag; every "
                             f"admission path must refuse work once "
                             f"drain starts, or a draining replica "
                             f"seats decodes the router already "
                             f"resumed elsewhere (double decode, "
                             f"broken exactly-once stream)")))
    return findings

"""device-sync-in-loop: host loops that feed jit entries must stay async.

The dispatch side of the simulator is pipelined: host loops (the extender
wave chains, the resident-delta folds, the scheduler pack loop) enqueue
jitted work and let XLA run ahead. One synchronous read inside such a
loop — ``.block_until_ready()``, ``np.asarray`` on a device array,
``float(arr)`` — stalls the pipeline every iteration: the host blocks on
step N before it can even *trace* step N+1, turning async dispatch into
lock-step round trips.

This rule flags those syncs when they sit inside a host ``for``/``while``
loop whose body also calls a jit entry point. ``np.asarray``/``float``/
``int`` only fire on values traced back (by local assignment) to a jit
entry's result — coercing genuine numpy state in the same loop is host
arithmetic, not a sync. A consolidated ``jax.device_get`` of many results
at once is the blessed idiom this rule pushes toward and is deliberately
NOT flagged; so is the tracing layer's ``fetch(tree, site)``, which is one
``jax.device_get`` inside a ``host-sync`` span. Syncs outside such loops
(epilogues, one-shot reads after a batch) are fine; traced code is the
purity rules' business and is excluded here. A deliberate per-iteration sync (e.g. a small mask the
host algorithm genuinely needs before the next dispatch) takes the
standard ``osim: lint-ok[device-sync-in-loop]`` comment escape with a
one-line justification.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from ..lint import Finding, LintContext, ModuleInfo, _find_function, rule
from .purity import _is_static_expr

RULE = "device-sync-in-loop"

#: jax-Array-only blocking calls — unambiguous syncs wherever they appear
_SYNC_ATTRS = {"block_until_ready", "item"}
#: numpy calls that pull a device array to host
_NP_PULLS = {"asarray", "array"}
_COERCERS = {"float", "int"}


def _is_fetch(ctx: LintContext, mod: ModuleInfo, func: ast.expr) -> bool:
    """``jax.device_get(...)``, or ``fetch(...)`` of a module named
    ``tracing`` (utils/tracing.fetch: one device_get in a host-sync span)."""
    if isinstance(func, ast.Attribute) and func.attr == "device_get":
        return True
    resolved = ctx.resolve_call(mod, func)
    return (
        resolved is not None
        and resolved[1] == "fetch"
        and resolved[0].rsplit(".", 1)[-1] == "tracing"
    )


def _is_jitish(
    ctx: LintContext, mod: ModuleInfo, func: ast.expr,
    cache: Dict[Tuple[str, str], bool],
) -> bool:
    """True when ``func`` resolves to a jit entry, or to a thin wrapper
    whose body calls one directly (``ops.grouped._group_call``-style
    dispatchers return device arrays just like the entry itself). A
    wrapper that itself calls ``jax.device_get`` or the tracing ``fetch``
    is host-returning (``schedule_scenarios_host``-style drivers do the one
    consolidated fetch internally) and is NOT jit-ish."""
    resolved = ctx.resolve_call(mod, func)
    if resolved is None:
        return False
    if resolved in cache:
        return cache[resolved]
    cache[resolved] = False  # cut recursion; one hop only below anyway
    tmod = ctx.modules.get(resolved[0])
    info = _find_function(tmod, resolved[1]) if tmod is not None else None
    result = False
    if info is not None:
        if info.is_jit_root:
            result = True
        else:
            calls_jit = fetches = False
            for node in ast.walk(info.node):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if _is_fetch(ctx, tmod, f):
                    fetches = True
                    break
                inner = ctx.resolve_call(tmod, f)
                if inner is not None:
                    iinfo = _find_function(ctx.modules[inner[0]], inner[1])
                    if iinfo is not None and iinfo.is_jit_root:
                        calls_jit = True
            result = calls_jit and not fetches
    cache[resolved] = result
    return result


def _calls_outside_fetch(
    ctx: LintContext, mod: ModuleInfo, expr: ast.expr
) -> Iterator[ast.Call]:
    """Calls in ``expr``, not descending into a fetch: what a fetch
    returns is host data, whatever it was computed from."""
    stack: List[ast.AST] = [expr]
    while stack:
        node = stack.pop()
        if isinstance(node, ast.Call):
            if _is_fetch(ctx, mod, node.func):
                continue
            yield node
        stack.extend(ast.iter_child_nodes(node))


def _device_names(
    ctx: LintContext, mod: ModuleInfo, fn_node: ast.AST,
    jitish_cache: Dict[Tuple[str, str], bool],
) -> Set[str]:
    """Names assigned (anywhere in the function) from a jit entry's
    result — the values whose coercion inside a loop is a device sync."""
    out: Set[str] = set()
    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Assign):
            continue
        produces_device = any(
            _is_jitish(ctx, mod, sub.func, jitish_cache)
            for sub in _calls_outside_fetch(ctx, mod, node.value)
        )
        if not produces_device:
            continue
        for target in node.targets:
            elts = target.elts if isinstance(target, ast.Tuple) else [target]
            for elt in elts:
                inner = (
                    elt.elts
                    if isinstance(elt, (ast.Tuple, ast.List))
                    else [elt]
                )
                for e in inner:
                    if isinstance(e, ast.Starred):
                        e = e.value
                    if isinstance(e, ast.Name):
                        out.add(e.id)
    return out


def _root_name(node: ast.expr) -> Optional[str]:
    while isinstance(node, (ast.Subscript, ast.Attribute, ast.Call)):
        node = node.func if isinstance(node, ast.Call) else node.value
    return node.id if isinstance(node, ast.Name) else None


def _walk_skipping(root: ast.AST, skip: Set[int]) -> Iterator[ast.AST]:
    """ast.walk, but do not descend into function defs whose id is in
    ``skip`` (jit-reachable nested defs are traced code, not host code)."""
    stack: List[ast.AST] = [root]
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if (
                isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef))
                and id(child) in skip
            ):
                continue
            stack.append(child)


def _feeds_jit(ctx: LintContext, mod: ModuleInfo, loop: ast.AST,
               skip: Set[int],
               jitish_cache: Dict[Tuple[str, str], bool]) -> str:
    """The jit entry a loop body calls, or '' when the loop is jit-free."""
    for node in _walk_skipping(loop, skip):
        if not isinstance(node, ast.Call):
            continue
        resolved = ctx.resolve_call(mod, node.func)
        if resolved is None:
            continue
        if _is_jitish(ctx, mod, node.func, jitish_cache):
            return f"{resolved[0]}:{resolved[1]}"
    return ""


def _device_arg(args: List[ast.expr], device_names: Set[str]) -> bool:
    for a in args:
        if _is_static_expr(a):
            continue
        root = _root_name(a)
        if root is not None and root in device_names:
            return True
    return False


def _sync_findings(
    mod: ModuleInfo, loop: ast.AST, skip: Set[int], jit_entry: str,
    device_names: Set[str],
) -> Iterator[Tuple[int, int, str]]:
    np_alias = mod.alias_for("numpy")
    for node in _walk_skipping(loop, skip):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in _SYNC_ATTRS:
            if fn.attr == "item" and node.args:
                continue
            yield (
                node.lineno, node.col_offset,
                f".{fn.attr}() inside a host loop feeding jit entry "
                f"{jit_entry} blocks the dispatch pipeline every iteration;"
                " hoist the sync out of the loop or batch the reads into"
                " one jax.device_get",
            )
        elif (
            isinstance(fn, ast.Attribute)
            and fn.attr in _NP_PULLS
            and isinstance(fn.value, ast.Name)
            and fn.value.id in np_alias
            and _device_arg(node.args, device_names)
        ):
            yield (
                node.lineno, node.col_offset,
                f"np.{fn.attr}() on a jit result inside a host loop feeding "
                f"{jit_entry} is a per-iteration device->host sync; batch "
                "the reads into one jax.device_get",
            )
        elif (
            isinstance(fn, ast.Name)
            and fn.id in _COERCERS
            and fn.id not in mod.functions
            and _device_arg(node.args, device_names)
        ):
            yield (
                node.lineno, node.col_offset,
                f"{fn.id}() on a jit result inside a host loop feeding "
                f"{jit_entry} is a per-iteration device->host sync; keep "
                "the loop async and read once at the end",
            )


@rule(
    RULE,
    ".block_until_ready()/np.asarray/float() on jit results inside host "
    "for/while loops that call jit entries stall the dispatch pipeline "
    "every iteration",
)
def device_sync_in_loop(ctx: LintContext) -> Iterator[Finding]:
    jitish_cache: Dict[Tuple[str, str], bool] = {}
    for mod in ctx.modules.values():
        # traced defs in this module: their bodies are compiler business
        reachable_ids = {
            id(i.node)
            for i in mod.functions.values()
            if (mod.name, i.qualname) in ctx.reachable
        }
        flagged: Set[Tuple[int, int]] = set()
        for info in {id(i.node): i for i in mod.functions.values()}.values():
            if (mod.name, info.qualname) in ctx.reachable:
                continue
            device_names = _device_names(ctx, mod, info.node, jitish_cache)
            for node in ast.walk(info.node):
                if not isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
                    continue
                jit_entry = _feeds_jit(ctx, mod, node, reachable_ids,
                                       jitish_cache)
                if not jit_entry:
                    continue
                for line, col, msg in _sync_findings(
                    mod, node, reachable_ids, jit_entry, device_names
                ):
                    if (line, col) in flagged:
                        continue  # nested loops / nested defs double-walk
                    flagged.add((line, col))
                    yield Finding(RULE, mod.path, line, col, msg)

"""use-after-donation: reading a buffer after donating it.

``donate_argnums`` hands an argument's device buffers to XLA for
in-place reuse: after the call, the Python object still exists but its
buffers are dead. Touching it again is at best a
``RuntimeError: invalid buffer``, at worst (through an executable that
aliased the pages) silent corruption or a crash deep inside the
runtime.

Detection is name-based and intra-module:

- a variable bound from ``jax.jit(..., donate_argnums=...)`` donates
  those positional args at every call site;
- a variable bound from one of the repo's donating step factories
  (``DONATING_FACTORIES`` below — all donate arg 0, the TrainState)
  donates arg 0, unless the call passes ``donate=False`` or
  ``jit=False`` (the raw, undonated body);
- at each call site, the donated NAME is tracked through the enclosing
  function in statement order: any later read before a rebinding is a
  finding. A donating call inside a loop whose donated name is never
  rebound in that loop donates the same dead buffer again on the next
  iteration — also a finding.

Donor bindings are flow-sensitive per scope: a function inherits the
donor names bound in its lexically enclosing scopes, its parameters
shadow them, and rebinding a name from a non-donating expression
clears its donor status — so a scope that uses ``step`` for an
unrelated callable is not polluted by another scope's
``step = make_train_step(...)``.

The safe idiom is the same-statement rebind the train loop uses:
``state, metrics = step_fn(state, batch)``.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from tensorflow_distributed_tpu.analysis.rules.common import (
    Finding, ModuleContext, qualname)

RULE = "use-after-donation"

# The repo's step builders that return a donating jitted callable
# (audited in this PR): every one donates argnum 0 — the TrainState —
# by default. Keyed by bare name so both plain and module-qualified
# imports match.
DONATING_FACTORIES = {
    "make_train_step": (0,),
    "make_multi_step": (0,),
    "make_local_sgd_train_step": (0,),
    "make_1f1b_train_step": (0,),
}


def _donated_argnums(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """Donated positions for a binding RHS, or None when not donating."""
    q = qualname(call.func)
    base = q.rsplit(".", 1)[-1]
    if q in ("jax.jit", "jit", "jax.pjit", "pjit"):
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                v = kw.value
                if isinstance(v, ast.Constant) and isinstance(
                        v.value, int):
                    return (v.value,)
                if isinstance(v, (ast.Tuple, ast.List)):
                    nums = tuple(e.value for e in v.elts
                                 if isinstance(e, ast.Constant))
                    return nums or None
        return None
    if base in DONATING_FACTORIES:
        for kw in call.keywords:
            if kw.arg in ("donate", "jit") and isinstance(
                    kw.value, ast.Constant) and kw.value.value is False:
                return None
        return DONATING_FACTORIES[base]
    return None


def _own_donor_bindings(scope: ast.AST) -> Dict[str, Tuple[int, ...]]:
    """Donor names bound by Assigns DIRECTLY in ``scope`` (nested
    function bodies excluded) — the seed a nested scope inherits."""
    out: Dict[str, Tuple[int, ...]] = {}
    stack = list(ast.iter_child_nodes(scope))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        if isinstance(node, ast.Assign) and isinstance(node.value,
                                                       ast.Call):
            nums = _donated_argnums(node.value)
            if nums is not None:
                for target in node.targets:
                    if isinstance(target, ast.Name):
                        out[target.id] = nums
        stack.extend(ast.iter_child_nodes(node))
    return out


def _inherited_donors(ctx: ModuleContext, scope: ast.AST
                      ) -> Dict[str, Tuple[int, ...]]:
    """Donor bindings visible to ``scope`` from its lexically
    enclosing scopes (module outward-in, so inner bindings win),
    minus names shadowed by the scope's own parameters."""
    chain: List[ast.AST] = [ctx.tree]
    fi = next((f for f in ctx.functions if f.node is scope), None)
    if fi is not None:
        enclosing = []
        cur = fi.scope
        while cur is not None:
            enclosing.append(cur.node)
            cur = cur.scope
        chain.extend(reversed(enclosing))
    donors: Dict[str, Tuple[int, ...]] = {}
    for s in chain:
        donors.update(_own_donor_bindings(s))
    args = getattr(scope, "args", None)
    if args is not None:
        for a in (args.args + args.posonlyargs + args.kwonlyargs
                  + [x for x in (args.vararg, args.kwarg) if x]):
            donors.pop(a.arg, None)
    return donors


def _enclosing_loop(ctx: ModuleContext, node: ast.AST) -> Optional[ast.AST]:
    cur = ctx.parent(node)
    while cur is not None:
        if isinstance(cur, (ast.For, ast.While)):
            return cur
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            return None
        cur = ctx.parent(cur)
    return None


def check(ctx: ModuleContext) -> Iterator[Finding]:
    scopes: List[ast.AST] = [ctx.tree] + [fi.node for fi in ctx.functions
                                          if not isinstance(fi.node,
                                                            ast.Lambda)]
    for scope in scopes:
        yield from _check_scope(ctx, scope)


def _check_scope(ctx: ModuleContext, scope: ast.AST) -> Iterator[Finding]:
    # Ordered traversal in EXECUTION order, not source order: an
    # Assign evaluates its value (loads, then the donation) before
    # binding its targets, so the safe same-statement rebind
    # ``state, m = step_fn(state, batch)`` clears the donation it
    # just recorded. ``donors`` is flow-sensitive: it starts from the
    # bindings inherited from enclosing scopes and is updated as
    # Assigns execute — a rebind from a non-donating expression clears
    # donor status, so shared names don't cross-contaminate.
    donors: Dict[str, Tuple[int, ...]] = _inherited_donors(ctx, scope)
    donated: Dict[str, ast.AST] = {}   # name -> donating call node

    def visit(node: ast.AST) -> Iterator[Finding]:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            return  # separate scope, separate pass
        if isinstance(node, ast.Assign):
            yield from visit(node.value)
            nums = (_donated_argnums(node.value)
                    if isinstance(node.value, ast.Call) else None)
            for target in node.targets:
                yield from visit(target)
                for n in ast.walk(target):
                    if isinstance(n, ast.Name):
                        if nums is not None and n is target:
                            donors[n.id] = nums
                        else:
                            donors.pop(n.id, None)
            return
        if isinstance(node, (ast.AugAssign, ast.AnnAssign)):
            if node.value is not None:
                yield from visit(node.value)
            yield from visit(node.target)
            if isinstance(node.target, ast.Name):
                donors.pop(node.target.id, None)
            return
        if isinstance(node, ast.Name):
            if isinstance(node.ctx, ast.Load) and node.id in donated:
                call = donated[node.id]
                if not ctx.suppressed(node, RULE):
                    # Pop only on an EMITTED finding (one per
                    # donation, no cascades); a suppressed read must
                    # not consume the budget and hide later real ones.
                    donated.pop(node.id)
                    yield ctx.finding(
                        node, RULE,
                        f"{node.id!r} read after being donated at line "
                        f"{call.lineno} — its buffers were handed to "
                        f"XLA and may already be reused")
            elif isinstance(node.ctx, (ast.Store, ast.Del)):
                donated.pop(node.id, None)
            return
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in donors):
            for child in ast.iter_child_nodes(node):
                yield from visit(child)   # argument loads come first
            for i in donors[node.func.id]:
                if i < len(node.args) and isinstance(node.args[i],
                                                     ast.Name):
                    name = node.args[i].id
                    donated[name] = node
                    loop = _enclosing_loop(ctx, node)
                    if loop is not None \
                            and not _stored_in(ctx, loop, name) \
                            and not ctx.suppressed(node, RULE):
                        yield ctx.finding(
                            node, RULE,
                            f"{name!r} is donated here but never "
                            f"rebound in the enclosing loop — the next "
                            f"iteration donates a dead buffer")
            return
        for child in ast.iter_child_nodes(node):
            yield from visit(child)

    body = scope.body if isinstance(scope.body, list) else [scope.body]
    for stmt in body:
        yield from visit(stmt)


def _stored_in(ctx: ModuleContext, loop: ast.AST, name: str) -> bool:
    for node in ast.walk(loop):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        if isinstance(node, ast.Name) and isinstance(
                node.ctx, (ast.Store, ast.Del)) and node.id == name:
            return True
    return False

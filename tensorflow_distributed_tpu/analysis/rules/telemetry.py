"""Telemetry schema contract — producer and consumer checks.

The cross-process telemetry contract lives in ``observe/schemas.py``:
every ``event=`` record kind with its declared field table. Two AST
passes hold the tree to it:

* **Producers** — every ``emit("kind", field=...)`` call (and every
  ``{"event": "kind", ...}`` dict literal, which covers the stdout
  run log and the supervisor's journal records) is checked against
  the kind's schema: undeclared kind, undeclared field, or a missing
  required field (only provable when the call has no ``**`` splat)
  is a finding. ``recovery`` records additionally get their literal
  ``kind=`` discriminator checked against ``RECOVERY_KINDS``.
* **Consumers** — in the three cross-process readers
  (``observe/report.py``, ``observe/fleetview.py``,
  ``fleet/router.py``), every literal
  ``rec.get("field")`` / ``rec["field"]`` read must name a field some
  producer declares (any kind, the common tags, the nested payload
  shapes, or an open family pattern) — a consumer can never read a
  field no producer can write.

Dynamic emits (``emit(kind_var, **fields)``) are invisible to the
static pass on purpose; ``MetricsRegistry(validate=True)`` (armed by
``--check``) covers them at runtime with the same tables.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator, List, Optional, Tuple

from tensorflow_distributed_tpu.analysis.rules.common import (
    Finding, ModuleContext, qualname)

RULE_KIND = "undeclared-record-kind"
RULE_FIELD = "undeclared-record-field"
RULE_REQUIRED = "missing-required-field"
RULE_READ = "undeclared-consumer-read"

_EMIT_NAMES = frozenset({"emit", "emit_event"})

#: The cross-process readers the consumer pass holds to the contract.
CONSUMER_SUFFIXES = ("observe/report.py", "observe/fleetview.py",
                     "fleet/router.py")


def _norm(path: str) -> str:
    return path.replace("\\", "/")


def _schemas():
    # Lazy: keeps rule registration import-light and avoids any
    # analysis <-> observe import cycle at module load.
    from tensorflow_distributed_tpu.observe import schemas
    return schemas


def _literal_kwargs(call: ast.Call) -> Tuple[List[Tuple[str, ast.AST]], bool]:
    literal: List[Tuple[str, ast.AST]] = []
    splat = False
    for kw in call.keywords:
        if kw.arg is None:
            splat = True
        else:
            literal.append((kw.arg, kw.value))
    return literal, splat


def _check_fields(ctx: ModuleContext, node: ast.AST, kind: str,
                  fields: List[Tuple[str, ast.AST]], splat: bool
                  ) -> Iterator[Finding]:
    sch = _schemas()
    schema = sch.schema_for(kind)
    if schema is None:
        if not ctx.suppressed(node, RULE_KIND):
            yield ctx.finding(
                node, RULE_KIND,
                f"record kind '{kind}' has no schema in "
                f"observe/schemas.py")
        return
    allowed = sch.allowed_fields(kind)
    for name, value in fields:
        if name in allowed or schema.open_fields \
                or sch.matches_pattern(kind, name):
            continue
        if not ctx.suppressed(node, RULE_FIELD):
            yield ctx.finding(
                node, RULE_FIELD,
                f"'{kind}' record field '{name}' is not declared in "
                f"its schema")
    if not splat:
        present = {name for name, _ in fields}
        tag_names = {f.name for f in sch.COMMON_TAGS}
        for f in schema.fields:
            if f.required and f.name not in present \
                    and f.name not in tag_names:
                if not ctx.suppressed(node, RULE_REQUIRED):
                    yield ctx.finding(
                        node, RULE_REQUIRED,
                        f"'{kind}' record is missing required field "
                        f"'{f.name}'")
    if kind == "recovery":
        for name, value in fields:
            if name == "kind" and isinstance(value, ast.Constant) \
                    and isinstance(value.value, str) \
                    and value.value not in sch.RECOVERY_KINDS:
                if not ctx.suppressed(node, RULE_KIND):
                    yield ctx.finding(
                        node, RULE_KIND,
                        f"recovery kind '{value.value}' is not in "
                        f"observe/schemas.RECOVERY_KINDS")


def _check_producers(ctx: ModuleContext) -> Iterator[Finding]:
    if _norm(ctx.path).endswith("observe/schemas.py"):
        return
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            callee = qualname(node.func).rsplit(".", 1)[-1]
            if callee not in _EMIT_NAMES and callee != "_emit":
                continue
            if not (node.args and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue  # dynamic kind: runtime validation's job
            literal, splat = _literal_kwargs(node)
            yield from _check_fields(ctx, node, node.args[0].value,
                                     literal, splat)
        elif isinstance(node, ast.Dict):
            kind: Optional[str] = None
            fields: List[Tuple[str, ast.AST]] = []
            splat = False
            for key, value in zip(node.keys, node.values):
                if key is None:
                    splat = True
                    continue
                if not (isinstance(key, ast.Constant)
                        and isinstance(key.value, str)):
                    continue
                if key.value == "event":
                    if isinstance(value, ast.Constant) \
                            and isinstance(value.value, str):
                        kind = value.value
                else:
                    fields.append((key.value, value))
            if kind is not None:
                yield from _check_fields(ctx, node, kind, fields, splat)


def _check_consumers(ctx: ModuleContext) -> Iterator[Finding]:
    npath = _norm(ctx.path)
    if not npath.endswith(CONSUMER_SUFFIXES):
        return
    sch = _schemas()
    universe = sch.consumer_universe()
    patterns = sch.consumer_patterns()

    def readable(name: str) -> bool:
        return name in universe or any(
            re.fullmatch(p, name) for p in patterns)

    for node in ast.walk(ctx.tree):
        name: Optional[str] = None
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "get" and node.args \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            recv = qualname(node.func.value)
            if recv.startswith("os.environ"):
                continue
            name = node.args[0].value
        elif isinstance(node, ast.Subscript) \
                and isinstance(node.ctx, ast.Load) \
                and isinstance(node.slice, ast.Constant) \
                and isinstance(node.slice.value, str):
            name = node.slice.value
        if name is None or readable(name):
            continue
        if not ctx.suppressed(node, RULE_READ):
            yield ctx.finding(
                node, RULE_READ,
                f"consumer reads field '{name}' that no producer "
                f"declares (observe/schemas.py)")


def check(ctx: ModuleContext) -> Iterator[Finding]:
    yield from _check_producers(ctx)
    yield from _check_consumers(ctx)

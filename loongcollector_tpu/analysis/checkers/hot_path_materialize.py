"""hot-path-materialize: per-event object churn on the columnar fast path.

loongcolumn's contract (docs/observability.md "Columnar event path"): groups
flow as arena-span columns from ingest to sink, and per-event Python
objects are minted ONLY at the instance-wrapper boundary of a plugin that
declared no columnar support — explicitly, counted in
``models.churn_stats()``.  Code in the hot scopes below that touches the
materializing surface silently re-introduces exactly the per-event
allocation the columnar plane removed (the dict path spent its time
building ``_contents`` tuples, not parsing).

Flagged in ``ops/`` and ``pipeline/serializer/`` (the device + wire hot
scopes):

* ``group.events`` attribute reads — the property materializes lazily;
* ``.materialize(...)`` / ``.to_dict(...)`` calls;
* per-event object construction (``LogEvent()`` … / ``add_log_event()`` …).

Flagged inside any class body declaring ``supports_columnar = True``
(columnar-capable processor/flusher plugins, wherever they live):

* ``.materialize(...)`` / ``.to_dict(...)`` calls and per-event object
  construction — a plugin that DECLARED it keeps groups columnar must not
  mint row objects in its own body.  (Plain ``.events`` reads stay legal
  there: capable plugins carry a row-path fallback for groups that arrive
  already materialized.)

Escape: ``# loonglint: disable=hot-path-materialize`` with a
justification — the canonical dict-path fallbacks in the serializers (the
non-ASCII / event-group routes json.dumps semantics require) and the
ingest-side PB decode carry it.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Tuple

from ..core import Checker, Finding, ModuleInfo, attr_tail, iter_functions

CHECK = "hot-path-materialize"

_SCOPES = ("/ops/", "/pipeline/serializer/")
_EVENT_CTORS = {"LogEvent", "MetricEvent", "SpanEvent", "RawEvent"}
_EVENT_ADDERS = {"add_log_event", "add_metric_event", "add_span_event",
                 "add_raw_event"}
_MATERIALIZING_CALLS = {"to_dict", "materialize"}


def _is_event_construction(node: ast.Call) -> bool:
    fn = node.func
    if isinstance(fn, ast.Name) and fn.id in _EVENT_CTORS:
        return True
    return attr_tail(node) in _EVENT_ADDERS


def _columnar_capable_classes(tree: ast.AST) -> List[ast.ClassDef]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        for stmt in node.body:
            if isinstance(stmt, ast.Assign) \
                    and any(isinstance(t, ast.Name)
                            and t.id == "supports_columnar"
                            for t in stmt.targets) \
                    and isinstance(stmt.value, ast.Constant) \
                    and stmt.value.value is True:
                out.append(node)
                break
    return out


class HotPathMaterializeChecker(Checker):
    name = CHECK
    description = ("no per-event object materialization (.events reads, "
                   ".to_dict()/materialize() calls, LogEvent construction) "
                   "in ops/, pipeline/serializer/, or columnar-capable "
                   "plugin bodies")

    def check_module(self, mod: ModuleInfo) -> Iterator[Finding]:
        relpath = "/" + mod.relpath
        funcs: List[Tuple[str, ast.AST]] = list(iter_functions(mod.tree))
        if any(scope in relpath for scope in _SCOPES):
            yield from self._check_scope(mod, mod.tree, funcs,
                                         flag_events_read=True)
            return
        # columnar-capable plugin bodies anywhere else in the tree
        for cls in _columnar_capable_classes(mod.tree):
            yield from self._check_scope(mod, cls, funcs,
                                         flag_events_read=False)

    def _check_scope(self, mod: ModuleInfo, root: ast.AST, funcs,
                     flag_events_read: bool) -> Iterator[Finding]:
        for node in ast.walk(root):
            if flag_events_read and isinstance(node, ast.Attribute) \
                    and node.attr == "events" \
                    and isinstance(node.ctx, ast.Load):
                yield Finding(
                    CHECK, mod.relpath, node.lineno, node.col_offset,
                    ".events read in a hot scope: the property "
                    "materializes per-event objects lazily — read span "
                    "columns (group.columns / group._events) instead, or "
                    "justify the dict fallback with a disable comment",
                    symbol=self._enclosing(funcs, node))
                continue
            if not isinstance(node, ast.Call):
                continue
            tail = attr_tail(node)
            if tail in _MATERIALIZING_CALLS:
                yield Finding(
                    CHECK, mod.relpath, node.lineno, node.col_offset,
                    f".{tail}() in a hot scope: materialization belongs "
                    "to the instance-wrapper boundary (counted in "
                    "models.churn_stats()), never inside the columnar "
                    "fast path",
                    symbol=self._enclosing(funcs, node))
            elif _is_event_construction(node):
                yield Finding(
                    CHECK, mod.relpath, node.lineno, node.col_offset,
                    "per-event object construction in a hot scope: the "
                    "columnar plane carries rows as arena spans — build "
                    "column vectors, not LogEvent objects",
                    symbol=self._enclosing(funcs, node))

    @staticmethod
    def _enclosing(funcs: List[Tuple[str, ast.AST]], node: ast.AST) -> str:
        best = ""
        for qn, fn in funcs:
            if (fn.lineno <= node.lineno
                    and node.lineno <= (fn.end_lineno or fn.lineno)):
                best = qn      # innermost wins: iteration is outside-in
        return best

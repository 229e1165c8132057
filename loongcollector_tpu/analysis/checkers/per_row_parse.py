"""per-row-parse: per-row Python parsing inside columnar-capable plugins.

loongstruct's contract (docs/device_plane.md "Structural indexing"):
columnar groups parse through whole-buffer passes — the native structural
index, the device kernel, or a vectorised numpy emitter.  A `json.loads`
or CSV-FSM call sitting inside a loop in a columnar-capable processor
body re-introduces exactly the per-row Python tail this plane retired
(every escape-bearing row once dropped to `json.loads`, and JSON ran at
a third of the simple-line rate for it).

Flagged inside any class body declaring ``supports_columnar = True``:

* ``json.loads(...)`` calls within a ``for``/``while`` loop or a
  comprehension / generator expression;
* calls to a per-row split helper (``*_fsm_split``) within the same.

Loops are what make these per-ROW: a single bounded probe (schema
discovery) outside a loop is fine.  Escape:
``# loonglint: disable=per-row-parse`` with a justification — the counted
fallback tiers (malformed rows demoted off the structural plane, deviant
rows under the numpy index) carry it, because they are the DESIGNED
slow path: counted in ``parse_fallback_rows_total`` and alarmed via
``PARSE_FALLBACK_DEGRADED`` when sustained.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Tuple

from ..core import Checker, Finding, ModuleInfo, attr_tail, iter_functions
from .hot_path_materialize import _columnar_capable_classes

CHECK = "per-row-parse"


def _is_json_loads(node: ast.Call) -> bool:
    fn = node.func
    return (isinstance(fn, ast.Attribute) and fn.attr == "loads"
            and isinstance(fn.value, ast.Name) and fn.value.id == "json")


def _is_fsm_split(node: ast.Call) -> bool:
    fn = node.func
    name = fn.id if isinstance(fn, ast.Name) else attr_tail(node)
    return bool(name) and name.endswith("_fsm_split")


class PerRowParseChecker(Checker):
    name = CHECK
    description = ("no per-row Python parsing (json.loads / CSV-FSM calls "
                   "inside loops) in columnar-capable plugin bodies — "
                   "parse from the structural index, or justify the "
                   "counted fallback tier with a disable comment")

    def check_module(self, mod: ModuleInfo) -> Iterator[Finding]:
        funcs: List[Tuple[str, ast.AST]] = list(iter_functions(mod.tree))
        loop_nodes = (ast.For, ast.While, ast.ListComp, ast.SetComp,
                      ast.DictComp, ast.GeneratorExp)
        for cls in _columnar_capable_classes(mod.tree):
            for loop in ast.walk(cls):
                if not isinstance(loop, loop_nodes):
                    continue
                for node in ast.walk(loop):
                    if not isinstance(node, ast.Call):
                        continue
                    if _is_json_loads(node):
                        what = "json.loads"
                    elif _is_fsm_split(node):
                        what = "per-row FSM split"
                    else:
                        continue
                    yield Finding(
                        CHECK, mod.relpath, node.lineno, node.col_offset,
                        f"{what} inside a loop in a columnar-capable "
                        "plugin body: rows parse per-event here — use the "
                        "structural-index plane (native/"
                        "ops.kernels.struct_index), or justify the "
                        "counted fallback tier with a disable comment",
                        symbol=self._enclosing(funcs, node))

    @staticmethod
    def _enclosing(funcs: List[Tuple[str, ast.AST]], node: ast.AST) -> str:
        best = ""
        for qn, fn in funcs:
            if (fn.lineno <= node.lineno
                    and node.lineno <= (fn.end_lineno or fn.lineno)):
                best = qn
        return best

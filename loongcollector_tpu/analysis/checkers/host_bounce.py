"""host-bounce: host pulls between two device dispatches in one function.

The loongresident contract (ops/fused_pipeline.py): consecutive
device-capable stages hand their intermediates to
each other IN HBM — one pack, one dispatch, one materialise.  A function
that dispatches a kernel, pulls the result to the host
(``np.asarray`` / ``jax.device_get`` / ``.block_until_ready()`` /
``DeviceFuture.result()``), and then dispatches again is exactly the
pack → H2D → dispatch → materialise → re-pack cycle fusion exists to
remove: each bounce costs a synchronous round trip per batch.

Flagged, in modules under ``ops/`` and in columnar-capable processor
bodies:

* a host-pull call whose statement sits BETWEEN two device-dispatch
  calls of the same function (straight-line bounce);
* a host-pull call inside a loop that also contains a device dispatch —
  the next iteration dispatches again, so the pull bounces per
  iteration.

A "device dispatch" is a call of ``donated_call`` / ``packed_call`` /
``staged`` or of any callable whose name mentions ``kernel``
(``self._dfa_kernel(...)``, ``sub_kern(...)`` …).  A single dispatch
followed by one materialise is the NORMAL end-of-pipeline shape and is
never flagged.

Escape: ``# loonglint: disable=host-bounce`` with a justification — the
designed fallback tiers carry it (the per-stage demotion path a faulted
fused chunk takes, the synchronous chunked classify loops of the
degraded routes), because they are counted exception paths, not the
steady state.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Tuple

from ..core import Checker, Finding, ModuleInfo, attr_tail, iter_functions
from .hot_path_materialize import _columnar_capable_classes

CHECK = "host-bounce"

_OPS_PREFIX = "loongcollector_tpu/ops/"
_PROC_PREFIX = "loongcollector_tpu/processor/"

_PULL_TAILS = {"asarray", "device_get", "block_until_ready", "result"}
_DISPATCH_TAILS = {"donated_call", "packed_call", "staged"}
_DISPATCH_NAMES = {"kern", "sub_kern"}


def _call_name(node: ast.Call) -> str:
    if isinstance(node.func, ast.Name):
        return node.func.id
    return attr_tail(node)


def _is_dispatch(node: ast.Call) -> bool:
    name = _call_name(node)
    low = name.lower()
    return (name in _DISPATCH_TAILS or name in _DISPATCH_NAMES
            or "kernel" in low)


def _is_pull(node: ast.Call) -> bool:
    name = _call_name(node)
    if name not in _PULL_TAILS:
        return False
    if name == "asarray":
        # np.asarray / jnp.asarray only — a bare asarray() helper is not
        # a host pull
        fn = node.func
        return (isinstance(fn, ast.Attribute)
                and isinstance(fn.value, ast.Name)
                and fn.value.id in ("np", "numpy", "jnp"))
    if name == "device_get":
        fn = node.func
        return (isinstance(fn, ast.Attribute)
                and isinstance(fn.value, ast.Name)
                and fn.value.id == "jax")
    return True


class HostBounceChecker(Checker):
    name = CHECK
    description = ("no host pulls (np.asarray / jax.device_get / "
                   ".block_until_ready / future.result) between two "
                   "device dispatches in one function under ops/ or a "
                   "columnar-capable processor body — compose the stages "
                   "into a fused program (ops/fused_pipeline), or justify "
                   "the fallback tier with a disable comment")

    def check_module(self, mod: ModuleInfo) -> Iterator[Finding]:
        if mod.relpath.startswith(_OPS_PREFIX):
            roots: List[ast.AST] = [mod.tree]
        elif mod.relpath.startswith(_PROC_PREFIX):
            roots = list(_columnar_capable_classes(mod.tree))
        else:
            return
        funcs: List[Tuple[str, ast.AST]] = []
        for root in roots:
            funcs.extend(iter_functions(root))
        seen = set()
        for qn, fn in funcs:
            if id(fn) in seen:
                continue
            seen.add(id(fn))
            yield from self._check_function(mod, qn, fn)

    def _check_function(self, mod: ModuleInfo, qualname: str,
                        fn: ast.AST) -> Iterator[Finding]:
        loops = [n for n in ast.walk(fn)
                 if isinstance(n, (ast.For, ast.While))]
        dispatch_lines: List[int] = []
        pulls: List[ast.Call] = []
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            if _is_dispatch(node):
                dispatch_lines.append(node.lineno)
                # a dispatch inside a loop runs again next iteration
                for lp in loops:
                    if lp.lineno <= node.lineno <= (lp.end_lineno
                                                    or lp.lineno):
                        dispatch_lines.append(node.lineno)
                        break
            elif _is_pull(node):
                pulls.append(node)
        if len(dispatch_lines) < 2 or not pulls:
            return
        lo, hi = min(dispatch_lines), max(dispatch_lines)
        loop_spans = []
        for lp in loops:
            span = (lp.lineno, lp.end_lineno or lp.lineno)
            if any(span[0] <= dl <= span[1] for dl in dispatch_lines):
                loop_spans.append(span)
        for node in pulls:
            # flagged when a LATER dispatch exists (line < hi): its input
            # was pulled to the host and re-packed.  A pull ON the first
            # dispatch's line (`a = np.asarray(k1(...))` before `k2(a)`)
            # is the canonical straight-line bounce; a pull at/after the
            # LAST dispatch is the normal final materialise — clean.
            between = lo <= node.lineno < hi
            in_dispatch_loop = any(a <= node.lineno <= b
                                   for a, b in loop_spans)
            if not (between or in_dispatch_loop):
                continue
            yield Finding(
                CHECK, mod.relpath, node.lineno, node.col_offset,
                f"host pull ({_call_name(node)}) between device "
                "dispatches: the result bounces through the host and the "
                "next stage re-packs it — compose these stages into one "
                "fused program (ops/fused_pipeline) or justify the "
                "fallback tier with a disable comment",
                symbol=qualname)

"""acquire-release: budget/slot/token acquisition must release on all paths.

The round-5 shape this exists to catch (ops/regex/engine.py,
PendingParse.dispatch pre-fix): a loop submits device chunks through
DevicePlane.submit — each submit acquires in-flight byte budget that only
DeviceFuture.result() releases — and appends the futures to a pending
list.  If pack/submit raises mid-loop, the already-submitted futures are
abandoned, the budget never returns, and every later dispatch stalls
forever: a liveness bug with no crash.

Today the pending list is the DeviceStream window (ops/device_stream.py),
the one place that leases, submits and releases; its owners (PendingParse,
FusedDispatch) loop over ``window.submit_rows(...)``, and the same shape
applies one level up: every chunk already in the window holds budget, a
ring slot and lane bytes until ``advance`` / ``drain`` settles it or
``abandon`` gives it up, so the loop must sit in a try whose handler
calls one of them.

Rule: a call to an acquire API whose returned obligation ESCAPES the
statement (stored into a container/attribute, or made in a loop) must be
lexically covered by a try that can discharge the obligation — a
``finally``, or an ``except`` handler that calls a release API (result /
release / drain / clear of the pending container) before re-raising.
A straight-line ``fut = plane.submit(...); fut.result()`` is fine: nothing
can raise between acquisition and the consume point taking ownership.

Acquire APIs (attr call + receiver filter, to stay quiet on unrelated
``.submit`` methods):

  .submit(...)    when the receiver mentions a device plane, or the call
                  passes the plane-protocol kwargs (nbytes / on_wait)
  .submit_rows(...) / .submit(...) on a receiver that mentions a stream
                  or a window: a chunk handed to the DeviceStream window
  ._acquire(...)  the raw budget primitive, same escape rules
  .lease(...)     loongstream batch-ring slots (receiver mentions a ring
                  OR a chip lane — loongmesh workers lease per-lane slots
                  on the same API): a leased BatchSlot escaping the
                  statement must be releasable on every path, exactly
                  like plane budget — a mid-loop pack/submit exception
                  (or an injected chip-lane fault raising between lease
                  and the pending append) that strands leased slots
                  starves the ring's pools and breaks the storm
                  conservation invariant (ring.leased_total() == 0)

loongfuse compile-cache handles (modules under ops/regex/): `open(...)`
and `np.load(...)` must be `with`-guarded (or try/finally-closed) — the
fused-DFA persistence path runs at pipeline (re)load, where a half-written
npz or a leaked handle survives for the process lifetime.  Stricter than
the escape rules above on purpose: cache I/O has no hot-path excuse to
hold a raw handle.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Tuple

from ..core import (Checker, Finding, ModuleInfo, ParentMap, attr_tail,
                    iter_functions, receiver_repr)

CHECK = "acquire-release"

_RELEASE_ATTRS = {
    "result", "release", "_release", "on_done", "drain", "close",
    "force_release", "clear", "cancel",
    # the DeviceStream window's verbs
    "advance", "abandon",
}


def _is_acquire_call(node: ast.Call) -> bool:
    tail = attr_tail(node)
    if tail == "_acquire":
        return True
    if tail == "lease":
        # ring-slot leases: `ring.lease(B, L)` / `batch_ring().lease(...)`,
        # and loongmesh per-lane leases (`lane.ring.lease(...)`, a
        # lane-keyed pool, or a chip-lane wrapper exposing .lease)
        recv = receiver_repr(node).lower()
        return "ring" in recv or "lane" in recv
    if tail not in ("submit", "submit_rows"):
        return False
    recv = receiver_repr(node).lower()
    if "window" in recv or "stream" in recv:
        return True
    if tail != "submit":
        return False
    if "plane" in recv:
        return True
    kwargs = {kw.arg for kw in node.keywords}
    return bool(kwargs & {"nbytes", "on_wait", "should_abort"})


def _guarding_try(parents: ParentMap, node: ast.AST,
                  func: ast.AST) -> bool:
    """True when an enclosing try (inside `func`) can discharge the
    obligation: it has a finally, or an except handler whose body reaches a
    release API call."""
    for anc in parents.ancestors(node):
        if anc is func:
            return False
        if isinstance(anc, ast.Try):
            if anc.finalbody:
                return True
            for handler in anc.handlers:
                for sub in ast.walk(handler):
                    if isinstance(sub, ast.Call) \
                            and attr_tail(sub) in _RELEASE_ATTRS:
                        return True
    return False


def _escapes(parents: ParentMap, node: ast.Call, func: ast.AST) -> str:
    """Does the acquired obligation outlive the statement in a way a later
    exception would strand?  Returns a reason string, or ''. """
    in_loop = any(isinstance(a, (ast.For, ast.While, ast.AsyncFor))
                  for a in _up_to(parents, node, func))
    parent = parents.parent(node)
    # plane.submit(...) used directly as an append/add argument
    if isinstance(parent, ast.Call) and \
            attr_tail(parent) in ("append", "add", "appendleft"):
        return "stored into a pending container"
    stmt = parent
    while stmt is not None and not isinstance(stmt, ast.stmt):
        stmt = parents.parent(stmt)
    if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
        targets = stmt.targets if isinstance(stmt, ast.Assign) \
            else [stmt.target]
        for tgt in targets:
            if isinstance(tgt, (ast.Attribute, ast.Subscript)):
                return "stored into an attribute/container"
    # `fut = submit(...)` then `pending.append(fut)` inside a loop is
    # covered by the loop rule: any iteration after the first can raise
    # while earlier futures are still owned
    if in_loop:
        return "acquired in a loop"
    return ""


def _up_to(parents: ParentMap, node: ast.AST, func: ast.AST):
    for anc in parents.ancestors(node):
        if anc is func:
            return
        yield anc


def _is_cache_handle_call(node: ast.Call) -> bool:
    """open() / np.load() in the fused compile-cache modules."""
    if isinstance(node.func, ast.Name) and node.func.id == "open":
        return True
    if attr_tail(node) == "load":
        recv = receiver_repr(node).lower()
        return recv in ("np", "numpy")
    return False


def _is_with_item(parents: ParentMap, node: ast.AST) -> bool:
    return isinstance(parents.parent(node), ast.withitem)


class AcquireReleaseChecker(Checker):
    name = CHECK
    description = ("device-budget / slot acquisition must release on all "
                   "paths (try/finally or except-drain); fuse compile-"
                   "cache file handles must be with-guarded")

    def check_module(self, mod: ModuleInfo) -> Iterator[Finding]:
        parents = ParentMap(mod.tree)
        cache_scope = "ops/regex/" in mod.relpath
        for qualname, func in iter_functions(mod.tree):
            if cache_scope:
                for node in ast.walk(func):
                    if not (isinstance(node, ast.Call)
                            and _is_cache_handle_call(node)):
                        continue
                    owner = next(
                        (a for a in parents.ancestors(node)
                         if isinstance(a, (ast.FunctionDef,
                                           ast.AsyncFunctionDef))), None)
                    if owner is not func:
                        continue
                    if _is_with_item(parents, node) \
                            or _guarding_try(parents, node, func):
                        continue
                    yield Finding(
                        CHECK, mod.relpath, node.lineno, node.col_offset,
                        "compile-cache file handle opened outside `with` "
                        "and without try/finally: a failure mid-write "
                        "leaks the handle (and can leave a torn cache "
                        "entry) for the process lifetime",
                        symbol=qualname)
            calls: List[Tuple[ast.Call, str]] = []
            for node in ast.walk(func):
                if isinstance(node, ast.Call) and _is_acquire_call(node):
                    # skip calls that belong to a nested def; they are
                    # reported against that def's own iteration
                    owner = next(
                        (a for a in parents.ancestors(node)
                         if isinstance(a, (ast.FunctionDef,
                                           ast.AsyncFunctionDef))), None)
                    if owner is func:
                        calls.append((node, attr_tail(node)))
            for node, tail in calls:
                reason = _escapes(parents, node, func)
                if not reason:
                    continue
                if _guarding_try(parents, node, func):
                    continue
                what = ("ring slot leased" if tail == "lease"
                        else "chunk put in flight" if tail == "submit_rows"
                        else "budget acquired")
                stranded = ("the leased ring slot"
                            if tail == "lease" else "the in-flight budget")
                yield Finding(
                    CHECK, mod.relpath, node.lineno, node.col_offset,
                    f"{what} via .{tail}() {reason} with no "
                    "enclosing try/finally or except-drain: an exception "
                    f"here strands {stranded} (the "
                    "PendingParse.dispatch leak shape)",
                    symbol=qualname)

"""compile_watch: shared jit-compile observability for every kernel family.

Before this module, only the fused-DFA cache counted its compiles
(``fuse_compile_total``) and only the fused-pipeline program cache
counted hits/misses — five of the seven kernel families compiled
invisibly, and the WidthAutoTuner's bucket-churn failure mode (a
flapping length bucket forcing a fresh XLA compile per flap) burned
silently.

``watched_jit(fn, family, **jit_kwargs)`` wraps ``jax.jit`` with the
per-geometry first-call proxy the repo already uses everywhere: jax
caches compiled executables per input shape, so the FIRST call of a
wrapper at a new geometry pays trace+compile (timed, counted as a cache
miss) and every later call at that geometry is a cache hit.  The wall
time recorded for a compile includes that first execution — it is the
first-dispatch cost the bench's warm-up window hides, which is exactly
the number ``extra.compile`` wants.

Per family this records:

  * ``jit_compile_total`` / ``jit_cache_hit_total`` counters and a
    ``jit_compile_ms`` histogram (labels: component=compile_watch,
    family=<family>) — fusion parity for the whole kernel vocabulary;
  * per-geometry compile counts + last compile wall-ms
    (``compile_status()``, the /debug/status ``compile`` section);
  * a one-shot ``RECOMPILE_STORM`` alarm when compiles inside the
    sliding window exceed the threshold, naming the churning family and
    its most recent geometry.  One alarm per episode: the flag re-arms
    only after the window drains empty (the storm ended).

The steady-state call path is one set-membership probe + one counter
add on top of the jitted call — the same order of cost as the
``dispatch_count += 1`` the kernel classes already pay.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

#: sliding storm window and the compile count inside it that trips the
#: alarm (≈ compiles/minute).  Module-level so tests (and operators via
#: monkeypatch) can tighten them; read at every compile note.
STORM_WINDOW_S = 60.0
STORM_COMPILES = 12


class _FamilyState:
    __slots__ = ("compiles", "cache_hits", "compile_ms_total",
                 "geometries", "recent", "alarmed", "episodes")

    def __init__(self) -> None:
        self.compiles = 0
        self.cache_hits = 0
        self.compile_ms_total = 0.0
        # geometry -> {"compiles": n, "last_ms": wall}
        self.geometries: Dict[str, dict] = {}
        # (perf_counter, geometry) of recent compiles, window-evicted
        self.recent: deque = deque()
        self.alarmed = False          # one alarm per storm episode
        self.episodes = 0


_lock = threading.Lock()
_families: Dict[str, _FamilyState] = {}
_records: Dict[str, object] = {}


def _family(name: str) -> _FamilyState:
    st = _families.get(name)
    if st is None:
        with _lock:
            st = _families.setdefault(name, _FamilyState())
    return st


def _record(family: str):
    rec = _records.get(family)
    if rec is None:
        with _lock:
            rec = _records.get(family)
            if rec is None:
                from ..monitor.metrics import MetricsRecord
                rec = MetricsRecord(category="component",
                                    labels={"component": "compile_watch",
                                            "family": family})
                _records[family] = rec
    return rec


def _compile_histogram(family: str):
    from ..monitor.metrics import shared_histogram
    return shared_histogram("jit_compile_ms",
                            labels={"component": "compile_watch",
                                    "family": family})


def _geometry_of(args: tuple, kwargs: dict) -> str:
    """Render the call geometry the way jax's executable cache keys it,
    best effort: array shapes, static scalars verbatim."""
    parts: List[str] = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is not None:
            parts.append("x".join(map(str, shape)) or "scalar")
        elif isinstance(a, (int, float, bool, str, bytes)):
            parts.append(repr(a))
        else:
            parts.append(type(a).__name__)
    for k in sorted(kwargs):
        a = kwargs[k]
        shape = getattr(a, "shape", None)
        parts.append(f"{k}=" + ("x".join(map(str, shape))
                                if shape is not None else repr(a)))
    return ",".join(parts)


def _note_hit(family: str) -> None:
    st = _family(family)
    with _lock:
        st.cache_hits += 1
    try:
        _record(family).counter("jit_cache_hit_total").add(1)
    except Exception:  # noqa: BLE001 — stats must never break dispatch
        pass


def _note_compile(family: str, geometry: str, wall_ms: float) -> None:
    now = time.perf_counter()
    alarm_doc: Optional[Tuple[int, int]] = None
    with _lock:
        st = _families.setdefault(family, _FamilyState())
        st.compiles += 1
        st.compile_ms_total += wall_ms
        row = st.geometries.setdefault(geometry,
                                       {"compiles": 0, "last_ms": 0.0})
        row["compiles"] += 1
        row["last_ms"] = round(wall_ms, 3)
        # sliding-window storm detection: evict aged compiles first — an
        # empty window is the episode boundary that re-arms the alarm
        horizon = now - STORM_WINDOW_S
        while st.recent and st.recent[0][0] < horizon:
            st.recent.popleft()
        if not st.recent:
            st.alarmed = False
        st.recent.append((now, geometry))
        if len(st.recent) >= STORM_COMPILES and not st.alarmed:
            st.alarmed = True
            st.episodes += 1
            alarm_doc = (len(st.recent),
                         len({g for _t, g in st.recent}))
    try:
        rec = _record(family)
        rec.counter("jit_compile_total").add(1)
        rec.counter("jit_compile_ms_total").add(int(wall_ms))
        _compile_histogram(family).observe(wall_ms)
    except Exception:  # noqa: BLE001
        pass
    if alarm_doc is not None:
        _send_storm_alarm(family, geometry, *alarm_doc)


def _send_storm_alarm(family: str, geometry: str, n_compiles: int,
                      n_geometries: int) -> None:
    """Outside _lock (the loonglint blocking-under-lock rule): the alarm
    manager takes its own lock and mirrors into the flight ring."""
    try:
        from ..monitor.alarms import AlarmLevel, AlarmManager, AlarmType
        AlarmManager.instance().send_alarm(
            AlarmType.RECOMPILE_STORM,
            f"jit recompile storm: family={family} recompiled "
            f"{n_compiles} times across {n_geometries} geometries in "
            f"{STORM_WINDOW_S:.0f}s; churning geometry {geometry}",
            level=AlarmLevel.ERROR,
            details={"family": family, "geometry": geometry,
                     "compiles_in_window": str(n_compiles),
                     "distinct_geometries": str(n_geometries)})
    except Exception:  # noqa: BLE001 — alarms must never break dispatch
        pass


class WatchedFn:
    """A jitted callable under compile accounting.  The per-geometry
    seen-set is per wrapper (matching jax's per-jit executable cache);
    the counters aggregate per FAMILY, so a kernel class re-instantiated
    per program still rolls up under one name."""

    __slots__ = ("_fn", "family", "_seen", "_seen_lock")

    def __init__(self, fn, family: str):
        self._fn = fn
        self.family = family
        self._seen: set = set()
        self._seen_lock = threading.Lock()

    def __call__(self, *args, **kwargs):
        geometry = _geometry_of(args, kwargs)
        if geometry in self._seen:           # steady state: one probe
            _note_hit(self.family)
            return self._fn(*args, **kwargs)
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        wall_ms = (time.perf_counter() - t0) * 1000.0
        with self._seen_lock:
            first = geometry not in self._seen
            self._seen.add(geometry)
        if first:
            _note_compile(self.family, geometry, wall_ms)
        else:
            # a concurrent first call beat us to the compile: jax's
            # cache made this a hit, count it as one
            _note_hit(self.family)
        return out

    # pass-throughs some call sites use on the raw jitted fn
    def __getattr__(self, name):
        return getattr(self._fn, name)


def watched_jit(fn, family: str, **jit_kwargs) -> WatchedFn:
    """``jax.jit(fn, **jit_kwargs)`` under compile accounting and under
    the name ``loong_<family>`` — the only sanctioned way to jit a kernel
    under ops/ (loonglint: unwatched-jit)."""
    import jax
    # the compiled module carries a name the program chose, whatever XLA
    # calls the ops inside it: the profiler's "XLA Modules" line reads
    # ``jit_loong_<family>(...)`` for every dispatch of the family
    try:
        fn.__name__ = fn.__qualname__ = "loong_" + family
    except (AttributeError, TypeError):
        pass        # a callable that cannot be renamed keeps its name
    return WatchedFn(jax.jit(fn, **jit_kwargs), family)


# ---------------------------------------------------------------------------
# status / reset


def compile_status() -> Dict[str, dict]:
    """Per-family compile ledger — the /debug/status ``compile`` section
    and the bench ``extra.compile`` source."""
    with _lock:
        out: Dict[str, dict] = {}
        for name in sorted(_families):
            st = _families[name]
            out[name] = {
                "compiles": st.compiles,
                "cache_hits": st.cache_hits,
                "compile_ms_total": round(st.compile_ms_total, 3),
                "storm_episodes": st.episodes,
                "geometries": {g: dict(row)
                               for g, row in sorted(st.geometries.items())},
            }
        return out


def reset_for_testing() -> None:
    with _lock:
        _families.clear()

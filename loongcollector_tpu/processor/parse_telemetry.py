"""Parse-fallback observability (loongstruct satellite).

The structural-index plane keeps well-formed rows off per-row Python; the
rows it CANNOT prove well-formed fall back per row — correct, but 100-1000x
slower per row.  A sustained malformed-row rate is therefore a silent
throughput collapse in the making (the same failure mode loongfuse's
`regex_tier_demotions` exists to surface on the regex tier), so every
fallback row is counted here:

* ``parse_fallback_rows_total`` / ``parse_rows_total`` counters on a
  per-processor MetricsRecord (exported through the exposition endpoint
  with ``processor=<plugin>`` labels);
* a one-shot ``PARSE_FALLBACK_DEGRADED`` alarm per (processor, pipeline)
  once the observed fallback rate is sustained (>= MIN_ROWS rows seen AND
  fallback fraction >= RATE_THRESHOLD), naming the pipeline and plugin;
* ``status()`` feeds the ``parse`` section of /debug/status.
"""

from __future__ import annotations

import threading
from typing import Dict, Tuple

#: alarm once a processor/pipeline has seen this many rows...
MIN_ROWS = 1024
#: ...with at least this fraction falling back per row
RATE_THRESHOLD = 0.05

_lock = threading.Lock()
_rows: Dict[Tuple[str, str], int] = {}
_fallback: Dict[Tuple[str, str], int] = {}
_drift: Dict[Tuple[str, str], int] = {}
#: (rows a native column call stored, such calls), only where one was made
_native: Dict[Tuple[str, str], Tuple[int, int]] = {}
_alarmed: set = set()
_records: Dict[str, object] = {}


def _metrics(processor: str):
    rec = _records.get(processor)
    if rec is None:
        # double-checked under the module lock: MetricsRecord.__init__
        # registers itself in WriteMetrics, so a racing double-create
        # would leave an orphaned duplicate series on /metrics
        from ..monitor.metrics import MetricsRecord
        with _lock:
            rec = _records.get(processor)
            if rec is None:
                rec = MetricsRecord(category="component",
                                    labels={"component": "loongstruct",
                                            "processor": processor})
                _records[processor] = rec
    return rec


def note_rows(processor: str, pipeline: str, total: int,
              fallback: int, drift: int = 0, native_rows: int = 0,
              native_calls: int = 0) -> None:
    """Account one group's parse outcome.  `fallback` = rows that left the
    structural plane for per-row Python; `drift` = rows parsed on-plane
    with schema drift (extras columns); `native_rows` = rows the group's
    `native_calls` native column calls stored (the timestamp processor's)."""
    if total <= 0:
        return
    try:
        rec = _metrics(processor)
        rec.counter("parse_rows_total").add(total)
        if fallback:
            rec.counter("parse_fallback_rows_total").add(fallback)
        if drift:
            rec.counter("parse_drift_rows_total").add(drift)
    except Exception:  # noqa: BLE001 — accounting must never break parsing
        pass
    key = (processor, pipeline)
    fire = False
    with _lock:
        _rows[key] = _rows.get(key, 0) + total
        _fallback[key] = _fallback.get(key, 0) + fallback
        if drift:
            _drift[key] = _drift.get(key, 0) + drift
        if native_calls:
            rows, calls = _native.get(key, (0, 0))
            _native[key] = (rows + native_rows, calls + native_calls)
        seen, fb = _rows[key], _fallback[key]
        if key not in _alarmed and seen >= MIN_ROWS \
                and fb >= seen * RATE_THRESHOLD:
            _alarmed.add(key)
            fire = True
    if fire:
        # outside _lock (loonglint blocking-under-lock rule)
        try:
            from ..monitor.alarms import AlarmLevel, AlarmManager, AlarmType
            AlarmManager.instance().send_alarm(
                AlarmType.PARSE_FALLBACK_DEGRADED,
                f"sustained per-row parse fallback on {processor}: "
                f"{fb}/{seen} rows off the structural plane",
                AlarmLevel.ERROR, pipeline=pipeline,
                details={"processor": processor,
                         "fallback_rows": str(fb), "rows": str(seen)})
        except Exception:  # noqa: BLE001
            pass


def status() -> Dict[str, object]:
    """The /debug/status `parse` section: per-(processor, pipeline) row /
    fallback / drift totals plus which pairs have alarmed; `native_rows` /
    `native_calls` where a native column call ran (absent, never 0, where
    none did: a process without the library)."""
    with _lock:
        rows = dict(_rows)
        fallback = dict(_fallback)
        drift = dict(_drift)
        native = dict(_native)
        alarmed = set(_alarmed)
    out = {}
    for key, seen in rows.items():
        label = "/".join(k for k in key if k) or key[0]
        out[label] = {
            "rows": seen,
            "fallback_rows": fallback.get(key, 0),
            "drift_rows": drift.get(key, 0),
            "degraded": key in alarmed,
        }
        if key in native:
            out[label]["native_rows"], out[label]["native_calls"] = native[key]
    return out


def reset_for_testing() -> None:
    """Clear accumulated state (counters records persist — they are
    process-lifetime instruments, like shared_histogram's)."""
    with _lock:
        _rows.clear()
        _fallback.clear()
        _drift.clear()
        _native.clear()
        _alarmed.clear()

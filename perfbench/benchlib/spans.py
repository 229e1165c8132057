"""Arithmetic for the readers of what PR 25 put into the program: the device
legs, reader and sink spans of the traced slice; the span-duration histograms
(``loong_span_seconds{name=...}``) and the ``file_input`` / ``startup``
sections of /debug/status over the whole window; the profiler's ``XLA
Modules`` line.  Every function returns None — never 0 — where the program
gave it nothing to read (a program from before that PR has none of these).
"""

from __future__ import annotations

import json
import sys

from . import observe, tracered

MODULES_LINE = "XLA Modules"
#: work on a cadence or at the collector's whim that stops the workers
PAUSE_SPANS = ("runtime.gc", "checkpoint.dump", "ledger.audit",
               "self_monitor.tick")
SPAN_HISTOGRAM = "loong_span_seconds"


def say(what: str, doc) -> None:
    """One line of standard error, before the comparison's: what a reader
    found beside its number (the harness prints nothing of these)."""
    sys.stderr.write(f"{what}: {json.dumps(doc, sort_keys=True)}\n")


# -- the traced slice's spans -----------------------------------------------------

def seconds_of(obs: dict, total=(), self_time=()):
    """Seconds of the slice's spans named in ``total``, plus the self time
    of those named in ``self_time`` (a span that holds others of the sum).
    None when no span of a name in ``total`` is there."""
    spans = obs.get("spans") or []
    if not any(s[0] in total for s in spans):
        return None
    out = sum(s[2] for s in spans if s[0] in total)
    if self_time and any(s[0] in self_time for s in spans):
        by = tracered.self_seconds(spans)
        out += sum(by.get(n, 0.0) for n in self_time)
    return out


def per_GB_in_slice(obs: dict, total=(), self_time=()):
    return observe.per_GB(obs, seconds_of(obs, total, self_time), True)


def makeup(obs: dict, suffix: str):
    """{seconds, self, children: {name: seconds}} of the slice's spans whose
    name ends ``suffix``: what a stage did itself beside what it waited for.
    None where there is no such span."""
    spans = obs.get("spans") or []
    ids = {s[3] for s in spans if s[0].endswith(suffix)}
    if not ids:
        return None
    children: dict = {}
    for name, _start, dur, _sid, parent, _attrs in spans:
        if parent in ids:
            children[name] = children.get(name, 0.0) + dur
    by = tracered.self_seconds([s for s in spans
                                if s[3] in ids or s[4] in ids])
    return {"seconds": sum(s[2] for s in spans if s[3] in ids),
            "self": sum(v for n, v in by.items() if n.endswith(suffix)),
            "children": children}


# -- histograms of the whole window ------------------------------------------------

def _series(metrics: dict, name: str, span: str) -> list:
    return [(lab, v) for lab, v in (metrics or {}).get(name, [])
            if lab.get("name") == span]


def span_histogram_delta(obs: dict, span: str):
    """(seconds, count, {le: count}) of ``span``'s duration histogram
    between the two scrapes; None where the later scrape has none."""
    later = _series(obs.get("metrics1"), SPAN_HISTOGRAM + "_count", span)
    if not later:
        return None

    def one(metrics, suffix):
        return sum(v for _l, v in _series(metrics, SPAN_HISTOGRAM + suffix,
                                          span))

    def buckets(metrics):
        out: dict = {}
        for lab, v in _series(metrics, SPAN_HISTOGRAM + "_bucket", span):
            out[lab["le"]] = out.get(lab["le"], 0.0) + v
        return out
    m0, m1 = obs.get("metrics0"), obs["metrics1"]
    b0, b1 = buckets(m0), buckets(m1)
    return (one(m1, "_sum") - one(m0, "_sum"),
            one(m1, "_count") - one(m0, "_count"),
            {le: c - b0.get(le, 0.0) for le, c in b1.items()})


def scrape_seconds(obs: dict) -> float:
    """Seconds between the two scrapes (the agent's own uptime at each;
    they bracket the window, the drain included), else the window's."""
    a = (obs.get("status0") or {}).get("uptime_s")
    b = (obs.get("status1") or {}).get("uptime_s")
    if a is not None and b is not None and b > a:
        return float(b - a)
    return float(obs["t1"] - obs["t0"])


def _pause_deltas(obs: dict) -> dict:
    """{span name: its histogram's delta} of the pause spans that are there."""
    by = {n: span_histogram_delta(obs, n) for n in PAUSE_SPANS}
    return {n: d for n, d in by.items() if d is not None}


def pause_share(obs: dict):
    """Seconds in the pause spans between the scrapes, over those seconds."""
    by = _pause_deltas(obs)
    if not by:
        return None
    say("pause spans between the scrapes (seconds, count)",
        {n: [round(d[0], 6), d[1]] for n, d in by.items()})
    say("the agent's span store at the window's end (/debug/status trace)",
        (obs.get("status1") or {}).get("trace"))
    return sum(d[0] for d in by.values()) / scrape_seconds(obs)


def pauses_over(obs: dict, seconds: float):
    """Pause spans longer than ``seconds`` between the scrapes: the count in
    the log2 buckets whose lower edge is at or above it."""
    over = {}
    for name, (_sum, count, buckets) in _pause_deltas(obs).items():
        under = [c for le, c in buckets.items()
                 if le not in ("+Inf", "inf") and float(le) <= seconds * 1.05]
        over[name] = count - (max(under) if under else 0.0)
    if not over:
        return None
    say(f"pause spans over {seconds * 1e3:g} ms between the scrapes", over)
    return sum(over.values())


# -- /debug/status -------------------------------------------------------------------

def section_delta(obs: dict, section: str):
    """{key: later − earlier} of a flat /debug/status section of counters
    (a nested dict of counters is summed); None where the later has none."""
    later = (obs.get("status1") or {}).get(section)
    if not later:
        return None
    earlier = (obs.get("status0") or {}).get(section) or {}

    def num(v):
        return float(sum(v.values())) if isinstance(v, dict) else float(v)
    return {k: num(v) - num(earlier.get(k, 0)) for k, v in later.items()}


def reader_blocked_share(obs: dict):
    d = section_delta(obs, "file_input")
    if d is None:
        return None
    held = d.get("reads_blocked_total", 0.0) + d.get("push_rejected_total", 0.0)
    tries = held + d.get("reads_total", 0.0)
    return held / tries if tries > 0 else None


def throttled_round_share(obs: dict):
    d = section_delta(obs, "file_input")
    if d is None or d.get("rounds_total", 0.0) <= 0:
        return None
    return d.get("rounds_throttled_total", 0.0) / d["rounds_total"]


def startup_gap(obs: dict, later: str, earlier: str):
    """Seconds from one start-up phase to a later one."""
    doc = (obs.get("status1") or {}).get("startup") or {}
    if later not in doc or earlier not in doc:
        return None
    return float(doc[later]) - float(doc[earlier])


# -- the device trace ------------------------------------------------------------------

def module_seconds(obs: dict, prefix: str):
    """Device seconds of the slice's ``XLA Modules`` events whose name starts
    ``prefix`` (the program names its jitted modules ``jit_loong_<family>``),
    and the seconds by name; None where there is none."""
    tr = obs.get("trace")
    if not tr:
        return None
    by: dict = {}
    for plane, line, name, start, dur in tr["events"]:
        if plane.startswith(tracered.DEVICE_PLANE) and line == MODULES_LINE \
                and name.startswith(prefix) \
                and tr["lo_ns"] <= float(start) < tr["hi_ns"]:
            key = name.split("(", 1)[0]
            by[key] = by.get(key, 0.0) + float(dur) / 1e9
    return (sum(by.values()), by) if by else None

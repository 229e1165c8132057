"""Arithmetic for the readers of what PR 36 put into the program: ``cpu_s`` and
``tid`` in every span's attributes (the CPU seconds of the thread that ran the
span, and the kernel's id of that thread), and the ``threads`` section of
/debug/status (the kernel's account of every thread: CPU seconds, seconds
runnable and waiting for a CPU, switches).  Wall time against work: a span's
seconds hold what its thread waited for — the interpreter lock, the device, a
full queue — and its CPU seconds do not.  Every function returns None — never
0 — where the program gave it nothing to read (a program from before that PR
has neither).
"""

from __future__ import annotations

import numpy as np

from . import observe, spans, tracered

WORKER_PREFIX = "processor-"
READER_THREAD = "file-server"
#: per-thread counters of ``threads.by_name`` that only grow
COUNTERS = ("cpu_s", "runq_wait_s", "timeslices", "voluntary_switches",
            "involuntary_switches")


# -- the traced slice's spans: work inside wall time ---------------------------------

def _attr(span, key):
    return (span[5] or {}).get(key)


def has_cpu(obs: dict) -> bool:
    """Whether the program's spans carry ``cpu_s`` at all."""
    return any("cpu_s" in (s[5] or {}) for s in obs.get("spans") or [])


def self_account(span_list, tid=None):
    """({name: [self seconds, self CPU seconds]}, {name: seconds left out}).

    Over the spans that carry a CPU reading (on thread ``tid`` where one is
    given): a span's seconds and CPU seconds less those of its children among
    them on the same thread.  A span whose ``cpu_s`` is None (a stopwatch, a
    span that changed threads) is left out on both sides — its seconds stay in
    its parent's self time, where its thread's CPU is too — and its wall
    seconds are in the second dict, so that what the account does not cover
    is said."""
    kept = {}
    left: dict = {}
    for s in span_list:
        cpu, on = _attr(s, "cpu_s"), _attr(s, "tid")
        if tid is not None and on != tid:
            continue
        if cpu is None:
            left[s[0]] = left.get(s[0], 0.0) + s[2]
        else:
            kept[s[3]] = (s, float(cpu), on)
    child_s: dict = {}
    child_cpu: dict = {}
    for s, cpu, on in kept.values():
        parent = kept.get(s[4])
        if parent is not None and parent[2] == on:
            child_s[s[4]] = child_s.get(s[4], 0.0) + s[2]
            child_cpu[s[4]] = child_cpu.get(s[4], 0.0) + cpu
    out: dict = {}
    for sid, (s, cpu, _on) in kept.items():
        row = out.setdefault(s[0], [0.0, 0.0])
        row[0] += max(s[2] - child_s.get(sid, 0.0), 0.0)
        row[1] += max(cpu - child_cpu.get(sid, 0.0), 0.0)
    return out, left


def worker_tid(obs: dict):
    """The thread most ``processor.*`` spans of the slice ran on."""
    count: dict = {}
    for s in obs.get("spans") or []:
        tid = _attr(s, "tid")
        if s[0].startswith("processor.") and tid is not None:
            count[tid] = count.get(tid, 0) + 1
    return max(count, key=count.get) if count else None


def self_cpu_seconds(obs: dict, prefix: str):
    """Self CPU seconds of the slice's spans whose name starts ``prefix``."""
    if not has_cpu(obs):
        return None
    by, _left = self_account(obs["spans"])
    rows = [v for n, v in by.items() if n.startswith(prefix)]
    return sum(r[1] for r in rows) if rows else None


def cpu_seconds_of(obs: dict, names):
    """CPU seconds of the slice's spans named in ``names``; None where none
    of them carries a reading."""
    got = [_attr(s, "cpu_s") for s in obs.get("spans") or [] if s[0] in names]
    got = [c for c in got if c is not None]
    return float(sum(got)) if got else None


def worker_offcpu_share(obs: dict):
    """Of the worker thread's accounted wall seconds in the slice, the part
    it spent off a CPU: 1 − Σ self CPU seconds / Σ self seconds."""
    tid = worker_tid(obs)
    if tid is None or not has_cpu(obs):
        return None
    by, left = self_account(obs["spans"], tid)
    wall = sum(r[0] for r in by.values())
    if wall <= 0:
        return None
    spans.say("the worker's account by span: [self seconds, self CPU seconds]"
              "; left out, with their wall seconds: the spans without a "
              "reading (stopwatches)",
              {"tid": tid,
               "left_out": {n: round(v, 6) for n, v in left.items()},
               "by_span": {n: [round(r[0], 6), round(r[1], 6)] for n, r in
                           sorted(by.items(), key=lambda kv: -kv[1][0])[:16]}})
    return 1.0 - sum(r[1] for r in by.values()) / wall


def cpu_by_name(obs: dict) -> dict:
    """{name: [seconds, CPU seconds, spans, spans without a reading]} over the
    slice: the check that no name's CPU passes its wall."""
    out: dict = {}
    for s in obs.get("spans") or []:
        row = out.setdefault(s[0], [0.0, 0.0, 0, 0])
        cpu = _attr(s, "cpu_s")
        row[2] += 1
        if cpu is None:
            row[3] += 1
        else:
            row[0] += s[2]
            row[1] += cpu
    return out


def makeup_cpu(obs: dict, suffix: str):
    """`spans.makeup` with the CPU seconds beside each entry:
    {seconds, self, children} → each a [seconds, CPU seconds] pair."""
    doc = spans.makeup(obs, suffix)
    if doc is None:
        return None
    all_spans = obs.get("spans") or []
    ids = {s[3] for s in all_spans if s[0].endswith(suffix)}
    cpu = sum(_attr(s, "cpu_s") or 0.0 for s in all_spans if s[3] in ids)
    child_cpu: dict = {}
    for s in all_spans:
        if s[4] in ids:
            child_cpu[s[0]] = child_cpu.get(s[0], 0.0) \
                + (_attr(s, "cpu_s") or 0.0)
    return {"seconds": [doc["seconds"], cpu],
            "self": [doc["self"], max(cpu - sum(child_cpu.values()), 0.0)],
            "children": {n: [v, child_cpu.get(n, 0.0)]
                         for n, v in doc["children"].items()}}


def idle_gaps_by_thread(obs: dict):
    """{thread: [[span name, device idle seconds], ...]}: the device's idle
    gaps of the slice by what EACH host thread was doing in them
    (`tracered.idle_gaps_by_span` over one thread's spans at a time), so a
    thread's column is its own whoever else started a span meanwhile.  Only
    spans with a CPU reading go in: a stopwatch (``cpu_s`` None) starts
    after the stage that opened it and stays open over the next ones, so it
    would take their gaps, and it does not say what its thread was doing."""
    tr, sl = obs.get("trace"), obs.get("slice")
    if not tr or not sl or not has_cpu(obs):
        return None
    a, b = sl
    lo_ns, hi_ns = tr["lo_ns"], tr["hi_ns"]
    ops = [o for o in tracered.device_ops(tr["events"])
           if lo_ns <= o[2] < hi_ns]

    def to_seconds(ns):
        return (np.asarray(ns) - lo_ns) / 1e9 + a
    names = {row.get("tid"): name for name, row in
             (((obs.get("status1") or {}).get("threads") or {})
              .get("by_name") or {}).items()}
    by_tid: dict = {}
    for s in obs["spans"]:
        if _attr(s, "cpu_s") is not None:
            by_tid.setdefault(_attr(s, "tid"), []).append(s)
    out = {}
    for tid, own in by_tid.items():
        gaps = tracered.idle_gaps_by_span(ops, own, a, b, to_seconds)
        out[str(names.get(tid, tid))] = [[n, round(v, 6)]
                                         for n, v in tracered.top(gaps, 8)]
    return out


# -- /debug/status threads: the kernel's account between the two scrapes -------------

def by_name_delta(obs: dict):
    """(seconds between the scrapes on the section's own clock, {thread name:
    {counter: later − earlier, "last_cpu": [earlier, later]}}, the same for
    ``other`` with its count of ``threads`` at both ends); None where the
    later scrape has no ``threads``.  A thread the
    earlier scrape does not know (or knows under another tid) started in
    between: its counters count from 0.  A counter the kernel did not give is
    absent from the difference too."""
    later = (obs.get("status1") or {}).get("threads")
    if not later or "by_name" not in later:
        return None
    earlier = (obs.get("status0") or {}).get("threads") or {}
    dt = float(later["at_s"]) - float(earlier.get("at_s", 0.0))
    if dt <= 0:
        return None

    def diff(new: dict, old: dict) -> dict:
        if old.get("tid") != new.get("tid"):
            old = {}
        out = {k: new[k] - old.get(k, 0) for k in COUNTERS
               if k in new and (k in old or not old)}
        for k in ("last_cpu", "threads"):      # states, at both ends
            if k in new:
                out[k] = [old.get(k), new[k]]
        return out
    before = earlier.get("by_name") or {}
    return (dt, {name: diff(row, before.get(name) or {})
                 for name, row in later["by_name"].items()},
            diff(later.get("other") or {}, earlier.get("other") or {}))


def busiest(deltas: dict, prefix: str):
    """The thread whose name starts ``prefix`` with the most CPU seconds
    between the scrapes: (name, its difference), or None."""
    rows = [(d["cpu_s"], name) for name, d in deltas.items()
            if name.startswith(prefix) and "cpu_s" in d]
    if not rows:
        return None
    name = max(rows)[1]
    return name, deltas[name]


def window_share(obs: dict, seconds):
    """``seconds`` counted between the two scrapes, as a share of the window.

    The scrapes bracket the window, its drain, and — in a traced run — the
    profiler's stop, which alone takes a minute or two of a fused cell's run
    while the agent idles (my chip run, PR 36, call 2: 132 and 219 s between
    the scrapes of a 45 s window), so seconds over the scrapes' distance
    would read a busy thread as idle.  What was counted goes with the bytes:
    the part of it that belongs to the window is the window's part of the
    bytes delivered between the scrapes, and that over the window's seconds
    is the share."""
    total = observe.delivered_bytes(obs, obs["t0"], float("inf"))
    if seconds is None or total <= 0:
        return None
    in_window = observe.delivered_bytes(obs) / total
    return seconds * in_window / (obs["t1"] - obs["t0"])


def thread_share(obs: dict, prefix: str, counter: str):
    """Δ``counter`` (seconds) of the busiest thread named ``prefix``…
    between the scrapes, as a share of the window (`window_share`)."""
    got = by_name_delta(obs)
    if got is None:
        return None
    _dt, deltas, _other = got
    top = busiest(deltas, prefix)
    if top is None or counter not in top[1]:
        return None
    return window_share(obs, top[1][counter])


def say_threads(obs: dict) -> None:
    """One line of standard error: the whole ``threads`` difference."""
    got = by_name_delta(obs)
    if got is None:
        return
    dt, deltas, other = got

    def tidy(d):
        return {k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in d.items()}
    spans.say("threads between the scrapes (/debug/status threads, later "
              f"less earlier, over {dt:.6f} s)",
              {"by_name": {n: tidy(d) for n, d in deltas.items()},
               "other": tidy(other)})


def delivered_MB_between_scrapes(obs: dict):
    """Input MB settled in the sink from the window's start to the end of its
    drain: what the agent delivered between the two scrapes (the earlier one
    is taken as the window opens, the later one once everything is settled)."""
    mb = observe.delivered_bytes(obs, obs["t0"], float("inf")) / 1e6
    return mb if mb > 0 else None


def worker_switches_per_MB(obs: dict):
    got = by_name_delta(obs)
    mb = delivered_MB_between_scrapes(obs)
    if got is None or mb is None:
        return None
    top = busiest(got[1], WORKER_PREFIX)
    if top is None or "voluntary_switches" not in top[1]:
        return None
    return top[1]["voluntary_switches"] / mb


def enqueue_blocked_share(obs: dict):
    """Seconds the worker waited at a full sender FIFO (/debug/status flush,
    ``enqueue_blocked_seconds`` summed over the sinks, later less earlier),
    as a share of the window (`window_share`)."""
    def total(status):
        sinks = (status or {}).get("flush")
        if not sinks:
            return None
        if not any("enqueue_blocked_seconds" in s for s in sinks.values()):
            return None
        return sum(float(s.get("enqueue_blocked_seconds", 0.0))
                   for s in sinks.values())
    later = total(obs.get("status1"))
    if later is None:
        return None
    return window_share(obs, later - (total(obs.get("status0")) or 0.0))

"""What a run observed, and the arithmetic the metric readers share.

``obs`` is a plain dict the harness fills (see harness.py, "observations").
A reader under ``metrics/`` takes ``obs`` and returns a number, or None when
the run gave it nothing to read; the harness then leaves the metric out.
"""

from __future__ import annotations

import numpy as np

from . import agent as agentmod
from . import roofline, stats, tracered


# -- the generator's and the tailer's side ------------------------------------

def settled_lines_at(obs: dict, t: float) -> int:
    """Lines whose fate was settled in the sink by time ``t``."""
    tail = obs["tail"]
    k = int(np.searchsorted(tail["t"], t, side="right"))
    return int(tail["last_seq"][k - 1]) + 1 if k else 0


def delivered_bytes(obs: dict, a: float = None, b: float = None) -> int:
    """Input bytes (lines with their newline) settled inside the window, or
    inside [a, b)."""
    a, b = (obs["t0"], obs["t1"]) if a is None else (a, b)
    return (settled_lines_at(obs, b) - settled_lines_at(obs, a)) \
        * obs["line_bytes"]


def due_lines(obs: dict):
    """(sequence numbers, due times) of every line due in the window."""
    w = obs["writes"]
    sel = (w["due"] >= obs["t0"]) & (w["due"] < obs["t1"])
    first, count, due = w["first"][sel], w["count"][sel], w["due"][sel]
    if not first.size:
        return np.empty(0, np.int64), np.empty(0)
    # writes are sequential: the lines of the selected writes, in order
    seqs = np.repeat(first - np.cumsum(count) + count, count) \
        + np.arange(int(count.sum()))
    return seqs, np.repeat(due, count)


def settle_times(obs: dict, seqs: np.ndarray) -> np.ndarray:
    """When each line's fate was settled (sink-visible time of the first
    record at or after it); infinity for one that never was."""
    tail = obs["tail"]
    t = np.append(tail["t"], np.inf)
    return t[np.searchsorted(tail["last_seq"], seqs, side="left")]


def latencies_ms(obs: dict) -> np.ndarray:
    """Sink-visible time minus due time of every line due in the window."""
    if "_lat" not in obs:
        seqs, due = due_lines(obs)
        obs["_lat"] = (settle_times(obs, seqs) - due) * 1e3
    return obs["_lat"]


def e2f_percentile(obs: dict, q: float):
    lat = latencies_ms(obs)
    return stats.percentile(lat, q) if lat.size else None


def gen_late_p99_ms(obs: dict):
    w = obs["writes"]
    sel = (w["due"] >= obs["t0"]) & (w["due"] < obs["t1"])
    if obs["traffic"]["mode"] != "open" or not sel.any():
        return None
    return stats.percentile((w["done"][sel] - w["due"][sel]) * 1e3, 99)


def _written_bytes_at(obs: dict, t: np.ndarray) -> np.ndarray:
    w = obs["writes"]
    cum = np.cumsum(w["count"]) * obs["line_bytes"]
    k = np.searchsorted(w["done"], t, side="right")
    return np.where(k > 0, cum[np.maximum(k - 1, 0)], 0)


def read_lag_bytes(obs: dict):
    """Bytes written less bytes the agent's reader had taken in, at each poll
    of /debug/ledger inside the window."""
    polls = [(t, n) for t, n in obs["polls"] if obs["t0"] <= t <= obs["t1"]]
    if not polls:
        return None
    t = np.array([p[0] for p in polls])
    read = np.array([p[1] for p in polls]) * obs["line_bytes"]
    return _written_bytes_at(obs, t) - read


def sink_flush_KiB_p50(obs: dict):
    tail = obs["tail"]
    sel = (tail["t"] >= obs["t0"]) & (tail["t"] < obs["t1"])
    sizes = np.diff(tail["bytes_end"], prepend=0)[sel]
    return float(np.median(sizes)) / 1024 if sizes.size else None


# -- /proc -----------------------------------------------------------------------

def agent_cpu_seconds(obs: dict) -> float:
    return obs["proc1"][1] - obs["proc0"][1]


def agent_cpu_cores(obs: dict) -> float:
    return agent_cpu_seconds(obs) / (obs["proc1"][0] - obs["proc0"][0])


def cpu_series(obs: dict):
    """Agent cores over each interval between two /proc samples in the
    window (the samples are a second apart, as the agent's own watchdog's)."""
    p = np.array([s for s in obs["proc"]
                  if obs["t0"] - 1e-3 <= s[0] <= obs["t1"] + 1e-3])
    if len(p) < 2:
        return np.empty(0)
    return np.diff(p[:, 1]) / np.diff(p[:, 0])


def throttled_share(obs: dict):
    """Share of the window's one-second samples in which the agent's CPU was
    over 0.7 of ``cpu_usage_limit`` — where the file server starts to stretch
    its sleeps (input/file/file_server.py)."""
    cores = cpu_series(obs)
    limit = float(obs["app_config"].get("cpu_usage_limit", 2.0))
    if not cores.size or limit <= 0:
        return None
    return float(np.mean(cores > 0.7 * limit))


# -- the agent's own counters ----------------------------------------------------

def _ring(status: dict) -> dict:
    return ((status or {}).get("streaming") or {}).get("ring") or {}


def device_row_share(obs: dict):
    """Rows that crossed the device in the window (the batch ring's real
    rows) over those and the rows routing kept on the host tiers (walker,
    per-row re) — all from /debug/status, so the share cannot pass 1."""
    def host(status):
        rows = (((status or {}).get("device") or {}).get("routing") or {}) \
            .get("rows") or {}
        return sum(rows.values())
    dev = _ring(obs["status1"]).get("real_rows", 0) \
        - _ring(obs["status0"]).get("real_rows", 0)
    kept = host(obs["status1"]) - host(obs["status0"])
    return dev / (dev + kept) if dev + kept > 0 else None


def pad_row_share(obs: dict):
    a, b = _ring(obs["status0"]), _ring(obs["status1"])
    real = b.get("real_rows", 0) - a.get("real_rows", 0)
    pad = b.get("padded_rows", 0) - a.get("padded_rows", 0)
    return pad / (real + pad) if real + pad > 0 else None


def compiles_in_window(obs: dict):
    def total(status):
        return sum(f.get("compiles", 0)
                   for f in ((status or {}).get("compile") or {}).values())
    if "compile" not in (obs["status1"] or {}):
        return None
    return float(total(obs["status1"]) - total(obs["status0"]))


def fused_dispatch_share(obs: dict):
    """Device dispatches served by a fused pipeline program, over all."""
    def fused(status):
        return ((status or {}).get("stage_fusion") or {}) \
            .get("fused_dispatch_total", 0)

    def all_(status):
        return ((status or {}).get("device") or {}).get("dispatched_total", 0)
    total = all_(obs["status1"]) - all_(obs["status0"])
    n_fused = fused(obs["status1"]) - fused(obs["status0"])
    if total <= 0:
        return None
    # the two counters are not bumped together: a dispatch counted as fused
    # but not yet as dispatched when the status page was read is still one
    return n_fused / max(total, n_fused)


def queue_wait_p50_ms(obs: dict):
    a = agentmod.histogram(obs["metrics0"], "loong_queue_wait_seconds",
                           component="process_queue")
    b = agentmod.histogram(obs["metrics1"], "loong_queue_wait_seconds",
                           component="process_queue")
    diff = {le: b[le] - a.get(le, 0.0) for le in b}
    q = stats.histogram_quantile(diff, 0.5)
    return None if q is None else q * 1e3


# -- spans -----------------------------------------------------------------------

def span_seconds(obs: dict, prefix: str, self_time: bool):
    spans = obs.get("spans")
    if not spans:
        return None
    if self_time:
        by = tracered.self_seconds(spans)
        return sum(v for k, v in by.items() if k.startswith(prefix))
    return sum(s[2] for s in spans if s[0].startswith(prefix))


def per_GB(obs: dict, seconds, traced_slice: bool = False):
    """``seconds`` per 10^9 input bytes delivered in the window, or — for
    what only the traced slice saw (spans) — in the slice."""
    if traced_slice and not obs.get("slice"):
        return None
    gb = delivered_bytes(obs, *(obs["slice"] if traced_slice else ())) / 1e9
    return None if seconds is None or gb <= 0 else seconds / gb


def span_p50_ms(obs: dict, name: str):
    d = [s[2] for s in obs.get("spans") or [] if s[0] == name]
    return float(np.median(d)) * 1e3 if d else None


# -- the device trace ------------------------------------------------------------

def device_busy(obs: dict):
    """(busy seconds, window seconds) of the traced window, or None."""
    tr = obs.get("trace")
    if not tr:
        return None
    ops = tracered.device_ops(tr["events"])
    if not ops:
        return None
    return tracered.busy_seconds(ops, tr["lo_ns"], tr["hi_ns"]), \
        (tr["hi_ns"] - tr["lo_ns"]) / 1e9


def device_idle_share(obs: dict):
    b = device_busy(obs)
    return None if b is None else 1.0 - b[0] / b[1]


def extract_calls(obs: dict):
    tr = obs.get("trace")
    if not tr:
        return []
    return tracered.kernel_calls(tracered.device_ops(tr["events"]), "_extract")


def extract_us_per_MiB(obs: dict):
    total_s = total_b = 0.0
    for text, sec in extract_calls(obs):
        shape = roofline.extract_shapes(text)
        if shape:
            total_s += sec
            total_b += shape[0] * shape[1]
    return total_s * 1e6 / (total_b / (1 << 20)) if total_b else None


def extract_roofline(obs: dict):
    """Bytes the calls had to move (from their shapes) over the chip's HBM
    peak, as a percentage of the device time the kernel took.  Bound: hbm."""
    total_s = total_b = 0.0
    for text, sec in extract_calls(obs):
        shape = roofline.extract_shapes(text)
        if shape:
            total_s += sec
            total_b += roofline.extract_bytes(*shape)
    if not total_s:
        return None
    peak = roofline.peak_of(obs["peaks"], obs["device"]["kind"])
    return roofline.hbm_roofline_pct(total_b, total_s, peak)

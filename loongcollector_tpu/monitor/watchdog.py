"""Agent self-watchdog: CPU/RSS sampling, adaptive throttling, limit breach.

Reference: core/monitor/Monitor.cpp (LogtailMonitor) — periodic self
CPU/memory sampling; exceeding limits triggers suicide-and-restart; the
realtime CPU level feeds file-input flow control
(file_server/event_handler/LogInput.cpp:176-200).

Here the breach action is a callback (the Application requests a restart or
logs critically) and the CPU level is exported for the FileServer's adaptive
sleep.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Callable, Optional

from .. import prof
from ..ops import device_info
from ..prof import flight
from ..utils import flags
from ..utils.logger import get_logger
from .alarms import AlarmLevel, AlarmManager, AlarmType
from .metrics import MetricsRecord

log = get_logger("watchdog")

flags.DEFINE_FLAG_DOUBLE("cpu_usage_limit", "agent CPU cores limit", 2.0)
flags.DEFINE_FLAG_INT32(
    "memory_usage_limit_mb",
    "agent RSS limit (MB), above what the device runtime holds resident",
    2048)


def _read_self_stat() -> tuple:
    """(utime+stime ticks, rss bytes) from /proc/self; comm-safe parse
    (field 2 may contain spaces — split after the last ')')."""
    with open("/proc/self/stat") as f:
        data = f.read()
    rest = data[data.rindex(")") + 2 :].split()
    ticks = int(rest[11]) + int(rest[12])
    rss_pages = int(rest[21])
    return ticks, rss_pages * os.sysconf("SC_PAGE_SIZE")


class LoongCollectorMonitor:
    def __init__(self, interval_s: float = 1.0,
                 on_limit_breach: Optional[Callable[[str], None]] = None):
        self.interval_s = interval_s
        self.on_limit_breach = on_limit_breach
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self.metrics = MetricsRecord(category="agent", labels={})
        self.cpu_gauge = self.metrics.gauge("cpu_cores")
        self.mem_gauge = self.metrics.gauge("memory_rss_bytes")
        self.cpu_level = 0.0  # 0..1 fraction of the limit, for flow control
        self._breach_streak = 0
        self._last_dump_path: Optional[str] = None
        self._episode_details: Optional[dict] = None

    def start(self) -> None:
        if self._running:
            return
        self._running = True
        self._thread = threading.Thread(target=self._run, name="watchdog",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._running = False
        if self._thread:
            self._thread.join(timeout=2)
            self._thread = None
        # retire the record: a stopped watchdog exports nothing further
        # (loonglint metric-naming ownership rule)
        self.metrics.mark_deleted()

    def _run(self) -> None:
        hz = os.sysconf("SC_CLK_TCK")
        last_ticks, _ = _read_self_stat()
        last_t = time.monotonic()
        while self._running:
            time.sleep(self.interval_s)
            try:
                ticks, rss = _read_self_stat()
            except OSError:
                continue
            now = time.monotonic()
            dt = max(now - last_t, 1e-6)
            cores = (ticks - last_ticks) / hz / dt
            last_ticks, last_t = ticks, now
            self.cpu_gauge.set(cores)
            self.mem_gauge.set(rss)
            cpu_limit = flags.get_flag("cpu_usage_limit")
            mem_limit = flags.get_flag("memory_usage_limit_mb") * 1024 * 1024
            self.cpu_level = min(cores / cpu_limit, 1.0) if cpu_limit > 0 else 0.0
            self._check_limits(cores, rss, cpu_limit, mem_limit)

    def _breach_details(self, breach: str) -> dict:
        """loongprof: a breach alarm must be diagnosable post-mortem —
        attach the flight-recorder dump path and the breaching thread's
        sampled stack to the alarm payload.  The flight event, the stack
        sample AND the dump all happen once per breach EPISODE (streak
        start): a sustained breach at 1 Hz must neither flood the flight
        ring with identical entries nor pay an all-thread stack walk per
        sample on an agent already over its CPU limit."""
        if self._episode_details is not None:
            return dict(self._episode_details, breach=breach)
        stack = prof.hottest_stack()
        flight.record("watchdog.breach", breach=breach)
        self._last_dump_path = flight.dump(reason="watchdog_breach")
        details = {"flight_dump": self._last_dump_path or "",
                   "breach": breach}
        if stack is not None:
            details["breach_thread"] = stack[0]
            details["breach_stack"] = stack[1][-1600:]
        self._episode_details = details
        return dict(details)

    def _check_limits(self, cores: float, rss: int, cpu_limit: float,
                      mem_limit: int) -> None:
        breach = None
        if cpu_limit > 0 and cores > cpu_limit:
            breach = f"cpu {cores:.2f} cores > limit {cpu_limit}"
            log.warning("watchdog: %s", breach)
            # stable message so AlarmManager aggregation collapses samples
            AlarmManager.instance().send_alarm(
                AlarmType.CPU_LIMIT, "agent cpu over limit",
                AlarmLevel.ERROR, details=self._breach_details(breach))
        # the limit guards the agent's own growth: the host memory the
        # device runtime made resident at backend start (device_info) is
        # the machine's fixed cost — on a v5e host it alone is several
        # times the limit, and counting it restarts a healthy agent
        runtime = device_info.runtime_rss_bytes()
        if rss - runtime > mem_limit > 0:
            breach = (f"rss {(rss - runtime)>>20} MB > limit "
                      f"{mem_limit>>20} MB")
            if runtime:
                breach += f" (device runtime's {runtime>>20} MB excluded)"
            log.warning("watchdog: %s", breach)
            AlarmManager.instance().send_alarm(
                AlarmType.MEM_LIMIT, "agent memory over limit",
                AlarmLevel.CRITICAL, details=self._breach_details(breach))
        if breach:
            self._breach_streak += 1
            # sustained breach (10 samples) triggers the restart action,
            # mirroring the reference's suicide-and-restart contract
            if self._breach_streak >= 10 and self.on_limit_breach:
                self.on_limit_breach(breach)
                self._breach_streak = 0
        else:
            self._breach_streak = 0
            # next episode gets a fresh dump, stack sample and flight entry
            self._last_dump_path = None
            self._episode_details = None

"""Start-up phases: when, since process start, the agent got where.

Always on, written once per phase: ``application.main`` marks
``imports_done`` (its own entry: the package is imported), ``backend_up``
(the device backend answered — jax's import and the TPU client's start lie
between the two), ``native_loaded`` (the native library built or loaded),
``pipelines_started`` (the first configuration applied, inputs running);
the device plane marks ``first_dispatch`` when the first dispatch has
materialised.  ``/debug/status`` serves them as ``startup``: seconds since
the process started, which /proc knows; where it does not, since this
module was imported.
"""

from __future__ import annotations

import os
import time
from typing import Dict, Optional

PHASES = ("imports_done", "backend_up", "native_loaded",
          "pipelines_started", "first_dispatch")


def _process_start_perf() -> float:
    """The process's start on the perf_counter clock."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat", "rb") as f:
            ticks = int(f.read().rsplit(b")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if age >= 0.0:
            return now - age
    except (OSError, ValueError, IndexError):
        pass
    return now


_t0 = _process_start_perf()
_phases: Dict[str, float] = {}


def mark(phase: str) -> None:
    """First call per phase wins (a restarted pipeline marks nothing)."""
    if phase not in _phases:
        _phases[phase] = round(time.perf_counter() - _t0, 6)


def status() -> Optional[dict]:
    """The /debug/status ``startup`` section; None before any mark."""
    return dict(_phases) or None

"""Which device this process computes on, and where its compiles are cached.

Every process that touches JAX (the agent, chip_smoke.py) calls
:func:`start` once, before its first compile.  It places the persistent
compile cache, initialises the backend, and refuses to run on a CPU the
operator did not ask for: CPU is reached only by an explicit pin
(``--cpu`` or ``JAX_PLATFORMS=cpu``), which tests and CPU drives use.  A
process that finds no accelerator and carries on anyway reports host
numbers under a device's name.

Nothing here runs at import, and importing this module does not import
jax: the parent of a chip-holding process (chip_smoke.py) stays off the
chip.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"

#: the compile cache's home when the environment names none: one fixed,
#: git-ignored directory inside the checkout.  The path is part of what a
#: later run must find again, so it is never derived from a data dir, a
#: temp dir, a pid or the clock.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")

_info: Optional[dict] = None


class NoAcceleratorError(RuntimeError):
    """JAX came up on the CPU and nobody pinned it there."""


def cpu_pinned(env=os.environ) -> bool:
    """True when the operator pinned the CPU backend through the
    environment (the ``--cpu`` flags pin it through ``start``)."""
    return env.get("JAX_PLATFORMS", "").strip().lower() == "cpu"


def configure_compile_cache() -> str:
    """Place JAX's persistent compile cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself — leave it
    alone and set no other in code.  Unset: :data:`DEFAULT_CACHE_DIR`.
    Every compile is cached (the default skips those under a second), so
    a warm start recompiles nothing."""
    import jax
    cache_dir = os.environ.get(ENV_CACHE_DIR)
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


def _rss_bytes() -> int:
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def start(force_cpu: bool = False) -> dict:
    """Bring the backend up and describe it (cached for the process).

    ``force_cpu`` is the ``--cpu`` flag.  Raises
    :class:`NoAcceleratorError` when the platform is ``cpu`` without a
    pin; a backend that fails to initialise raises jax's own error."""
    global _info
    if _info is not None:
        return _info
    import jax
    import jaxlib
    if force_cpu:
        jax.config.update("jax_platforms", "cpu")
    cache_dir = configure_compile_cache()
    # The TPU runtime maps host memory resident when its client starts
    # (~13 GB on a one-chip v5e host).  It is a fixed cost of the machine,
    # not the agent's working set: measured here so the self-watchdog can
    # hold its memory limit to what the agent itself grows by.
    rss_before = _rss_bytes()
    devices = jax.devices()
    runtime_rss = max(_rss_bytes() - rss_before, 0)
    platform = devices[0].platform
    if platform == "cpu" and not (force_cpu or cpu_pinned()):
        raise NoAcceleratorError(
            "JAX found no accelerator (platform 'cpu') and the CPU was not "
            "pinned; pass --cpu or set JAX_PLATFORMS=cpu to run on the "
            "host on purpose")
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    _info = {
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu_version,
        "compile_cache_dir": cache_dir,
        "runtime_rss_bytes": runtime_rss,
    }
    return _info


def status() -> Optional[dict]:
    """The description :func:`start` produced, or None before it ran
    (observe-only: /debug/status never initialises a backend)."""
    return _info


def runtime_rss_bytes() -> int:
    """Host memory the device runtime made resident at backend start; 0
    before :func:`start`."""
    return _info["runtime_rss_bytes"] if _info is not None else 0

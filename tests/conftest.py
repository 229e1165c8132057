"""Test config: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding tests run against
8 virtual CPU devices (SURVEY.md environment notes).  The CPU pin is the
plain ``JAX_PLATFORMS=cpu``, set before anything imports jax.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import faulthandler  # noqa: E402
import signal  # noqa: E402

# Device-plane stalls used to surface as opaque `timeout -k` kills with no
# stacks.  Make every hang diagnosable:
#  * SIGSEGV/SIGABRT/etc dump all thread stacks (faulthandler.enable);
#  * the tier-1 wrapper's SIGTERM (timeout(1)) dumps stacks too, then the
#    follow-up SIGKILL still ends the process;
#  * a watchdog dumps stacks shortly BEFORE the 870 s tier-1 budget so a
#    wedged run self-reports even if the signal never lands.
_crash_stream = None


def _dump_then_terminate(signum, frame):
    # dump all thread stacks, then die with the DEFAULT SIGTERM semantics
    # — plain faulthandler.register would swallow the signal and leave a
    # `timeout` without -k waiting forever on a process that never exits
    if _crash_stream is not None:
        faulthandler.dump_traceback(file=_crash_stream, all_threads=True)
        _crash_stream.flush()
    signal.signal(signal.SIGTERM, signal.SIG_DFL)
    signal.raise_signal(signal.SIGTERM)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long soaks excluded from tier-1 (-m 'not slow'); "
        "scripts/soak.sh runs them")
    # The dump must reach the REAL stderr: during a test, pytest's
    # fd-level capture points fd 2 at a per-test temp file that dies with
    # the process.  At conftest IMPORT capture is already active (fd 2 is
    # the temp file), but around pytest_configure the capture manager
    # suspends it — fd 2 is the original pipe/tty here, so dup it now.
    global _crash_stream
    _crash_stream = os.fdopen(os.dup(2), "w")
    faulthandler.enable(file=_crash_stream)
    try:
        signal.signal(signal.SIGTERM, _dump_then_terminate)
    except ValueError:  # not the main thread (embedded runner)
        pass
    faulthandler.dump_traceback_later(840, exit=False, file=_crash_stream)


def pytest_sessionfinish(session, exitstatus):
    faulthandler.cancel_dump_traceback_later()


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_device_stream_state():
    """loongstream isolation: the batch ring's slot pools and the width
    auto-tuner's floors/flush deadline are process-global; a test must not
    inherit another test's tuned geometry (a shrunken B floor changes the
    chunk sizes the watermark/budget tests are calibrated to)."""
    from loongcollector_tpu.ops import device_stream
    device_stream.reset_for_testing()
    yield


@pytest.fixture(autouse=True)
def _fresh_ack_watermark_state():
    """loongcrash isolation: the ack-watermark tracker and the recovery
    manager are process-global; a (dev, inode) registered authoritative by
    one test's FileServer must not skew another test's checkpoint dump if
    the kernel recycles the inode for a new tmp file."""
    yield
    from loongcollector_tpu import recovery
    from loongcollector_tpu.runner import ack_watermark
    ack_watermark.tracker().reset()
    recovery.reset()


def wait_for(cond, timeout=10.0, interval=0.05):
    """Shared sink-side poll helper: True iff cond() holds within timeout."""
    import time
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False

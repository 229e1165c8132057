"""loonglint: the tier-1 static-analysis gate plus per-checker fixtures.

Two layers:

1. `TestTier1Gate` runs the REAL full-tree scan — a loonglint violation
   anywhere in loongcollector_tpu/ fails the suite, and the allowlist is
   held to its <= 10 entry budget.  This is how the checkers are "wired
   into tier-1": the pytest gate cannot be skipped without skipping
   tier-1 itself.

2. Fixture tests feed each checker known-bad source (including a faithful
   excerpt of the round-5 PendingParse.dispatch budget leak,
   ops/regex/engine.py:513 pre-fix) and assert it is caught, plus the
   known-good variants to pin down precision.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from loongcollector_tpu.analysis import (Finding, ModuleInfo, Program,
                                         load_allowlist, run_analysis)
from loongcollector_tpu.analysis.checkers import all_checkers, checker_names
from loongcollector_tpu.analysis.checkers.acquire_release import \
    AcquireReleaseChecker
from loongcollector_tpu.analysis.checkers.blocking_locks import \
    BlockingUnderLockChecker
from loongcollector_tpu.analysis.checkers.registry_consistency import \
    RegistryConsistencyChecker
from loongcollector_tpu.analysis.checkers.tracing_hygiene import \
    TracingHygieneChecker
from loongcollector_tpu.analysis.core import (ALLOWLIST_BUDGET,
                                              default_allowlist_path)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def scan(src, checker, relpath="loongcollector_tpu/ops/fixture.py",
         extra_modules=()):
    """Run one checker over inline fixture source; returns findings."""
    mod = ModuleInfo("/fx/" + relpath, relpath, textwrap.dedent(src))
    mods = [mod] + [ModuleInfo("/fx/" + rp, rp, textwrap.dedent(s))
                    for rp, s in extra_modules]
    findings = list(checker.check_module(mod))
    for extra in mods[1:]:
        findings += list(checker.check_module(extra))
    findings += list(checker.finalize(Program("/fx", mods)))
    return findings


def checks_of(findings):
    return {f.check for f in findings}


# ---------------------------------------------------------------------------
# 1. the tier-1 gate


class TestTier1Gate:
    def test_full_tree_scan_is_clean(self):
        result = run_analysis()
        assert result.files_scanned > 100, "scan missed the package tree"
        assert result.ok, (
            "loonglint violations in the tree:\n"
            + "\n".join(f.format() for f in result.findings)
            + "\n".join(result.parse_errors))

    def test_allowlist_within_budget(self):
        entries = load_allowlist(default_allowlist_path())
        assert len(entries) <= ALLOWLIST_BUDGET, (
            f"allowlist has {len(entries)} entries; budget is "
            f"{ALLOWLIST_BUDGET} — pay down debt instead of parking more")

    def test_cli_json_contract(self):
        proc = subprocess.run(
            [sys.executable, "-m", "loongcollector_tpu.analysis", "--json"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        doc = json.loads(proc.stdout)
        assert doc["ok"] is True
        assert doc["allowlist_entries"] <= doc["allowlist_budget"]
        assert doc["files_scanned"] > 100

    def test_all_fifteen_checkers_registered(self):
        names = checker_names()
        assert names == ["acquire-release", "blocking-under-lock",
                         "tracing-hygiene", "registry-consistency",
                         "swallowed-fault", "unledgered-drop",
                         "metric-naming", "hot-path-materialize",
                         "per-row-parse", "unbounded-window",
                         "host-bounce", "reload-unsafe",
                         "raceguard-guarded-by", "stamp-propagation",
                         "unwatched-jit"]
        assert len(all_checkers()) == 15


# ---------------------------------------------------------------------------
# 2. acquire-release fixtures


# Faithful excerpt of ops/regex/engine.py:513 BEFORE the round-5 fix: the
# dispatch loop submits device chunks (acquiring plane budget) and appends
# the futures with no exception guard — a mid-loop pack/submit failure
# strands every already-acquired chunk's budget forever.
ENGINE_513_LEAK = """
class PendingParse:
    def dispatch(self, device_idx):
        plane = DevicePlane.instance()
        self.kern = self.engine._device_kernel()
        max_bucket = LENGTH_BUCKETS[-1]
        for chunk in _chunks(device_idx, MAX_BATCH):
            d_off = self.offsets[chunk]
            d_len = self.lengths[chunk]
            L = pick_length_bucket(int(d_len.max())) or max_bucket
            batch = pack_rows(self.arena, d_off, d_len, L)
            fut = plane.submit(self.kern, (batch.rows, batch.lengths),
                               batch.rows.nbytes,
                               on_wait=self._drain_if_pending)
            self._chunks_pending.append((chunk, batch, fut, self.kern))
"""

ENGINE_513_FIXED = """
class PendingParse:
    def dispatch(self, device_idx):
        plane = DevicePlane.instance()
        self.kern = self.engine._device_kernel()
        try:
            for chunk in _chunks(device_idx, MAX_BATCH):
                batch = pack_rows(self.arena, chunk)
                fut = plane.submit(self.kern, (batch.rows, batch.lengths),
                                   batch.rows.nbytes,
                                   on_wait=self._drain_if_pending)
                self._chunks_pending.append((chunk, batch, fut, self.kern))
        except BaseException:
            for _, _, fut, _k in self._chunks_pending:
                fut.release()
            self._chunks_pending.clear()
            raise
"""


class TestAcquireRelease:
    def test_flags_the_engine_513_leak_shape(self):
        findings = scan(ENGINE_513_LEAK, AcquireReleaseChecker())
        assert len(findings) == 1
        f = findings[0]
        assert f.check == "acquire-release"
        assert f.symbol == "PendingParse.dispatch"
        assert "strands the in-flight budget" in f.message

    def test_fixed_dispatch_is_clean(self):
        assert scan(ENGINE_513_FIXED, AcquireReleaseChecker()) == []

    def test_try_finally_is_clean(self):
        src = """
        def pump(plane, kern, chunks):
            futs = []
            try:
                for c in chunks:
                    futs.append(plane.submit(kern, (c,), c.nbytes))
            finally:
                for f in futs:
                    f.result()
        """
        assert scan(src, AcquireReleaseChecker()) == []

    def test_straight_line_submit_consume_is_clean(self):
        src = """
        def one(plane, kern, batch):
            fut = plane.submit(kern, (batch,), batch.nbytes)
            return fut.result()
        """
        assert scan(src, AcquireReleaseChecker()) == []

    # loongstream (ISSUE 6): batch-ring slot leases obey the same
    # acquire/release pairing as plane budget.  The leak-on-exception
    # shape: slots leased in a loop with no guard — a mid-loop failure
    # strands every already-leased slot (ring.leased_total() never
    # returns to 0, the storm conservation invariant).
    RING_LEASE_LEAK = """
    def pump(ring, arena, chunks, out):
        for chunk in chunks:
            slot = ring.lease(256, 128)
            out.append(slot.pack(arena, chunk))
    """

    RING_LEASE_FIXED = """
    def pump(ring, arena, chunks, out):
        leased = []
        try:
            for chunk in chunks:
                slot = ring.lease(256, 128)
                leased.append(slot)
                out.append(slot.pack(arena, chunk))
        except BaseException:
            for slot in leased:
                slot.release()
            raise
    """

    # the real streaming-dispatch shape (engine.PendingParse.dispatch):
    # inner try releases the just-leased slot, outer except-drain releases
    # everything already pending — both layers discharge the obligation
    RING_LEASE_STREAMING = """
    class PendingParse:
        def dispatch(self, ring, plane, device_idx):
            try:
                for chunk in _chunks(device_idx, MAX_BATCH):
                    slot = ring.lease(256, 128)
                    try:
                        batch = slot.pack(self.arena, chunk)
                        fut = plane.submit(self.kern,
                                           (batch.rows, batch.lengths),
                                           batch.rows.nbytes)
                    except BaseException:
                        slot.release()
                        raise
                    self._chunks_pending.append((chunk, batch, slot, fut))
            except BaseException:
                for _, _, slot, fut in self._chunks_pending:
                    fut.release()
                    slot.release()
                self._chunks_pending.clear()
                raise
    """

    def test_ring_lease_leak_on_exception_flagged(self):
        findings = scan(self.RING_LEASE_LEAK, AcquireReleaseChecker())
        assert len(findings) == 1
        f = findings[0]
        assert f.check == "acquire-release"
        assert "ring slot leased" in f.message
        assert "strands the leased ring slot" in f.message

    def test_ring_lease_guarded_is_clean(self):
        assert scan(self.RING_LEASE_FIXED, AcquireReleaseChecker()) == []

    def test_streaming_dispatch_shape_is_clean(self):
        assert scan(self.RING_LEASE_STREAMING, AcquireReleaseChecker()) == []

    # the shape after the dispatch loops moved into the DeviceStream
    # window: the owner loops over window.submit_rows, and every chunk
    # already in the window holds budget, slot and lane bytes — the loop
    # must sit in a try whose handler abandons (or drains) the window
    WINDOW_LOOP_LEAK = """
    class PendingParse:
        def dispatch(self, device_idx):
            window = self._window
            for chunk in _chunks(device_idx, MAX_BATCH):
                if not window.admit(len(chunk)):
                    continue
                window.submit_rows(self.call, self.arena,
                                   self.offsets[chunk],
                                   self.lengths[chunk], tag=chunk)
    """

    WINDOW_LOOP_ABANDONS = """
    class PendingParse:
        def dispatch(self, device_idx):
            window = self._window
            try:
                for chunk in _chunks(device_idx, MAX_BATCH):
                    if not window.admit(len(chunk)):
                        continue
                    window.submit_rows(self.call, self.arena,
                                       self.offsets[chunk],
                                       self.lengths[chunk], tag=chunk)
            except BaseException:
                window.abandon()
                raise
    """

    @pytest.mark.parametrize("fixture,flagged", [
        ("WINDOW_LOOP_LEAK", True), ("WINDOW_LOOP_ABANDONS", False)],
        ids=["bare_loop_flagged", "abandon_is_clean"])
    def test_window_submit_loop_needs_an_abandon(self, fixture, flagged):
        findings = scan(getattr(self, fixture), AcquireReleaseChecker())
        if not flagged:
            assert findings == []
            return
        assert len(findings) == 1
        assert "chunk put in flight" in findings[0].message
        assert findings[0].symbol == "PendingParse.dispatch"

    def test_unrelated_lease_receiver_ignored(self):
        # `.lease()` on things that aren't rings (a DHCP client, say)
        # stays out of scope — the receiver filter keeps precision
        src = """
        def renew(dhcp, ifaces, out):
            for i in ifaces:
                out.append(dhcp.lease(i))
        """
        assert scan(src, AcquireReleaseChecker()) == []

    # loongmesh (ISSUE 9): per-lane slot leases.  The leak-on-chip-fault
    # shape: a lane-bound dispatch loop leases slots and fires the
    # chip-lane fault point BETWEEN the lease and the pending append — an
    # injected single-chip fault (ChipLaneFault at dispatch) unwinds the
    # loop with the fresh slot AND every already-pending one stranded.
    LANE_LEASE_CHIP_FAULT_LEAK = """
    def dispatch_on_lane(lane, plane, arena, chunks, pending):
        for chunk in chunks:
            slot = lane.ring.lease(256, 128)
            batch = slot.pack(arena, chunk)
            fut = plane.submit(lane_gated(lane, kern),
                               (batch.rows, batch.lengths),
                               batch.rows.nbytes)
            pending.append((chunk, batch, slot, fut, lane))
    """

    LANE_LEASE_CHIP_FAULT_FIXED = """
    def dispatch_on_lane(lane, plane, arena, chunks, pending):
        try:
            for chunk in chunks:
                slot = lane.ring.lease(256, 128)
                try:
                    batch = slot.pack(arena, chunk)
                    fut = plane.submit(lane_gated(lane, kern),
                                       (batch.rows, batch.lengths),
                                       batch.rows.nbytes)
                except BaseException:
                    slot.release()
                    raise
                pending.append((chunk, batch, slot, fut, lane))
        except BaseException:
            for _, b, slot, fut, ln in pending:
                fut.release()
                slot.release()
            pending.clear()
            raise
    """

    def test_lane_lease_leak_on_chip_fault_flagged(self):
        findings = scan(self.LANE_LEASE_CHIP_FAULT_LEAK,
                        AcquireReleaseChecker())
        assert len(findings) >= 1
        assert any("ring slot leased" in f.message for f in findings)

    def test_lane_lease_guarded_is_clean(self):
        assert scan(self.LANE_LEASE_CHIP_FAULT_FIXED,
                    AcquireReleaseChecker()) == []

    # loongfuse: the fused-kernel geometry-cache pattern — a lazily-built
    # per-geometry kernel whose persistence layer touches cache files.
    # The kernel build itself is clean (no obligations); the cache I/O
    # must be with-guarded inside ops/regex/ modules.
    FUSED_GEOMETRY_CACHE_CLEAN = """
    import numpy as np

    class FusedSetExecFx:
        def _device_kernel(self):
            with self._kernel_lock:
                if self._kernel is None:
                    self._kernel = build_kernel(self.fdfa)
                return self._kernel

        def _load_cache(self, path):
            with np.load(path, allow_pickle=False) as z:
                return dict(z)

        def _save_cache(self, path, arrays):
            with open(path + ".tmp", "wb") as f:
                np.savez(f, **arrays)
            replace(path + ".tmp", path)
    """

    FUSED_CACHE_RAW_HANDLE = """
    import numpy as np

    def save_cache(path, arrays):
        f = open(path + ".tmp", "wb")
        np.savez(f, **arrays)
        f.close()
    """

    FUSED_CACHE_RAW_LOAD = """
    import numpy as np

    def load_cache(path):
        z = np.load(path, allow_pickle=False)
        return dict(z)
    """

    def test_fused_geometry_cache_pattern_is_clean(self):
        assert scan(self.FUSED_GEOMETRY_CACHE_CLEAN, AcquireReleaseChecker(),
                    relpath="loongcollector_tpu/ops/regex/fixture_fuse.py"
                    ) == []

    def test_fused_cache_raw_open_flagged(self):
        findings = scan(self.FUSED_CACHE_RAW_HANDLE, AcquireReleaseChecker(),
                        relpath="loongcollector_tpu/ops/regex/fixture_fuse.py")
        assert len(findings) == 1
        assert "compile-cache file handle" in findings[0].message

    def test_fused_cache_raw_np_load_flagged(self):
        findings = scan(self.FUSED_CACHE_RAW_LOAD, AcquireReleaseChecker(),
                        relpath="loongcollector_tpu/ops/regex/fixture_fuse.py")
        assert len(findings) == 1

    def test_cache_handle_rule_scoped_to_regex_modules(self):
        # the same raw open() OUTSIDE ops/regex/ is not this rule's
        # business — general handle hygiene belongs to the
        # ResourceWarning sweep
        assert scan(self.FUSED_CACHE_RAW_HANDLE, AcquireReleaseChecker(),
                    relpath="loongcollector_tpu/flusher/fixture.py") == []

    def test_raw_acquire_in_loop_flagged(self):
        src = """
        def drain(plane, sizes):
            for n in sizes:
                plane._acquire(n)
                process(n)
                plane._release(n)
        """
        findings = scan(src, AcquireReleaseChecker())
        assert checks_of(findings) == {"acquire-release"}

    def test_inline_suppression(self):
        src = ENGINE_513_LEAK.replace(
            "            fut = plane.submit(",
            "            # loonglint: disable=acquire-release\n"
            "            fut = plane.submit(")
        mod = ModuleInfo("/fx/a.py", "loongcollector_tpu/ops/a.py",
                         textwrap.dedent(src))
        findings = list(AcquireReleaseChecker().check_module(mod))
        assert len(findings) == 1
        # the runner consults mod.suppressed — verify the wiring
        assert mod.suppressed(findings[0].line, findings[0].check)


# The loongshard multi-lane shape (ISSUE 4): N workers each own a lane
# holding an in-flight dispatch whose budget only that lane's completion
# releases.  A dispatch loop that parks futures across SEVERAL lanes must
# discharge every lane on failure — completing just the current one leaves
# the other lanes' budget stranded (the multi-worker generalisation of the
# single-TLS-slot assumption the old runner made).
MULTI_LANE_LEAK = """
class ShardDispatcher:
    def dispatch_all(self, plane, kern, shards):
        for worker_id, batch in shards:
            fut = plane.submit(kern, (batch.rows,), batch.rows.nbytes,
                               on_wait=self._drain_own)
            self.lanes[worker_id].put((batch, fut))
"""

MULTI_LANE_FIXED = """
class ShardDispatcher:
    def dispatch_all(self, plane, kern, shards):
        try:
            for worker_id, batch in shards:
                fut = plane.submit(kern, (batch.rows,), batch.rows.nbytes,
                                   on_wait=self._drain_own)
                self.lanes[worker_id].put((batch, fut))
        except BaseException:
            for lane in self.lanes:
                pending = lane.take()
                if pending is not None:
                    pending[1].release()
            raise
"""


class TestMultiLaneAcquireRelease:
    def test_unguarded_multi_lane_dispatch_flagged(self):
        findings = scan(MULTI_LANE_LEAK, AcquireReleaseChecker(),
                        relpath="loongcollector_tpu/runner/fixture.py")
        assert checks_of(findings) == {"acquire-release"}

    def test_lane_draining_handler_is_clean(self):
        assert scan(MULTI_LANE_FIXED, AcquireReleaseChecker(),
                    relpath="loongcollector_tpu/runner/fixture.py") == []


# ---------------------------------------------------------------------------
# 3. blocking-under-lock fixtures


class TestBlockingUnderLock:
    def test_sleep_under_lock_flagged(self):
        src = """
        import threading, time
        class Pump:
            def __init__(self):
                self._lock = threading.Lock()
            def run(self):
                with self._lock:
                    time.sleep(1.0)
        """
        findings = scan(src, BlockingUnderLockChecker())
        assert checks_of(findings) == {"blocking-under-lock"}
        assert "time.sleep" in findings[0].message

    def test_future_result_under_lock_flagged(self):
        src = """
        class Pump:
            def drain(self):
                with self._lock:
                    data = self.fut.result()
        """
        findings = scan(src, BlockingUnderLockChecker())
        assert checks_of(findings) == {"blocking-under-lock"}

    def test_condition_wait_on_held_lock_is_clean(self):
        # the device-plane shape: Condition.wait releases the lock it
        # guards — the one legal blocking wait
        src = """
        class Plane:
            def _acquire_wait(self):
                with self._freed:
                    self._freed.wait(timeout=0.05)
        """
        assert scan(src, BlockingUnderLockChecker()) == []

    def test_dict_get_under_lock_is_clean(self):
        src = """
        class Manager:
            def lookup(self, key):
                with self._lock:
                    return self._queues.get(key)
        """
        assert scan(src, BlockingUnderLockChecker()) == []

    def test_blocking_queue_get_under_lock_flagged(self):
        src = """
        class Manager:
            def pump(self):
                with self._lock:
                    item = self.in_queue.get()
        """
        findings = scan(src, BlockingUnderLockChecker())
        assert checks_of(findings) == {"blocking-under-lock"}

    def test_flight_record_under_lock_flagged(self):
        # loongprof rule: the flight recorder must never be called with a
        # lock held — transition sites buffer and emit after release
        # (runner/circuit.py _emit)
        src = """
        import threading
        from loongcollector_tpu.prof import flight
        class Breaker:
            def __init__(self):
                self._lock = threading.Lock()
            def trip(self):
                with self._lock:
                    flight.record("breaker.open", sink=self.name)
        """
        findings = scan(src, BlockingUnderLockChecker())
        assert checks_of(findings) == {"blocking-under-lock"}
        assert "flight-recorder" in findings[0].message

    def test_flight_recorder_attribute_under_lock_flagged(self):
        src = """
        import threading
        class Owner:
            def __init__(self):
                self._lock = threading.Lock()
            def note(self):
                with self._lock:
                    self._recorder.record("ev", n=1)
        """
        findings = scan(src, BlockingUnderLockChecker())
        assert checks_of(findings) == {"blocking-under-lock"}

    def test_flight_record_outside_lock_is_clean(self):
        src = """
        import threading
        from loongcollector_tpu.prof import flight
        class Breaker:
            def __init__(self):
                self._lock = threading.Lock()
            def trip(self):
                with self._lock:
                    self._state = 1
                flight.record("breaker.open", sink=self.name)
        """
        assert scan(src, BlockingUnderLockChecker()) == []

    def test_unrelated_record_receiver_is_clean(self):
        # `.record()` on a non-flight receiver (a metrics store, a WAL)
        # is not the flight recorder — precision matters
        src = """
        import threading
        class Store:
            def __init__(self):
                self._lock = threading.Lock()
            def add(self):
                with self._lock:
                    self.journal.record("row")
        """
        assert scan(src, BlockingUnderLockChecker()) == []

    def test_lock_ordering_cycle_detected(self):
        src = """
        import threading
        class Alpha:
            def __init__(self):
                self._lock = threading.Lock()
            def alpha_push(self):
                with self._lock:
                    self.beta.beta_push()
        class Beta:
            def __init__(self):
                self._lock = threading.Lock()
            def beta_push(self):
                with self._lock:
                    self.alpha.alpha_drain()
        class AlphaPeer:
            def __init__(self):
                self._lock = threading.Lock()
            def alpha_drain(self):
                with self._lock:
                    self.alpha.alpha_push()
        """
        src2 = """
        import threading
        class Gamma:
            pass
        """
        findings = scan(src, BlockingUnderLockChecker(),
                        relpath="loongcollector_tpu/runner/fx.py",
                        extra_modules=[
                            ("loongcollector_tpu/runner/fx2.py", src2)])
        order = [f for f in findings if f.check == "lock-ordering"]
        assert order, "expected a lock-order cycle report"
        assert "Alpha._lock" in order[0].message
        assert "Beta._lock" in order[0].message

    def test_consistent_order_has_no_cycle(self):
        src = """
        import threading
        class Outer:
            def __init__(self):
                self._lock = threading.Lock()
            def outer_push(self):
                with self._lock:
                    self.inner.inner_push()
        class Inner:
            def __init__(self):
                self._lock = threading.Lock()
            def inner_push(self):
                with self._lock:
                    pass
        """
        findings = scan(src, BlockingUnderLockChecker(),
                        relpath="loongcollector_tpu/runner/fx.py")
        assert [f for f in findings if f.check == "lock-ordering"] == []


# ---------------------------------------------------------------------------
# 4. tracing-hygiene fixtures


class TestTracingHygiene:
    def test_time_in_jit_flagged(self):
        src = """
        import time, jax
        @jax.jit
        def kernel(rows):
            t0 = time.time()
            return rows + 1
        """
        findings = scan(src, TracingHygieneChecker())
        assert checks_of(findings) == {"tracing-hygiene"}
        assert "time.time" in findings[0].message

    def test_print_in_pallas_kernel_flagged(self):
        src = """
        from jax.experimental import pallas as pl
        def _kern(rows_ref, out_ref):
            print("debug", rows_ref)
            out_ref[...] = rows_ref[...]
        def build(rows):
            return pl.pallas_call(_kern, out_shape=None)(rows)
        """
        findings = scan(src, TracingHygieneChecker())
        assert checks_of(findings) == {"tracing-hygiene"}
        assert "print" in findings[0].message

    def test_factory_closure_is_traced(self):
        # the repo idiom: self._fn = jax.jit(build_fn(program))
        src = """
        import time, jax
        def build_fn(program):
            def run(rows, lengths):
                time.sleep(0.001)
                return rows
            return run
        fn = jax.jit(build_fn(None))
        """
        findings = scan(src, TracingHygieneChecker())
        assert checks_of(findings) == {"tracing-hygiene"}

    def test_np_asarray_in_jit_flagged(self):
        src = """
        import jax
        import numpy as np
        @jax.jit
        def kernel(rows):
            host = np.asarray(rows)
            return host
        """
        findings = scan(src, TracingHygieneChecker())
        assert checks_of(findings) == {"tracing-hygiene"}

    def test_float_cast_of_traced_param_flagged(self):
        src = """
        import jax
        @jax.jit
        def kernel(x):
            return float(x)
        """
        findings = scan(src, TracingHygieneChecker())
        assert checks_of(findings) == {"tracing-hygiene"}

    def test_host_code_outside_ops_not_scanned(self):
        src = """
        import time, jax
        @jax.jit
        def kernel(rows):
            return time.time()
        """
        assert scan(src, TracingHygieneChecker(),
                    relpath="loongcollector_tpu/runner/fx.py") == []

    def test_untraced_host_helper_is_clean(self):
        src = """
        import time
        def host_side(batch):
            t0 = time.time()
            return batch, t0
        """
        assert scan(src, TracingHygieneChecker()) == []

    def test_static_shape_math_is_clean(self):
        # int()/float() on non-parameter statics is trace-time shape math
        src = """
        import jax
        @jax.jit
        def kernel(rows):
            width = int(SOME_STATIC)
            return rows[:width]
        """
        assert scan(src, TracingHygieneChecker()) == []


# ---------------------------------------------------------------------------
# 5. registry-consistency fixtures


FAKE_ALARMS = """
class AlarmType:
    SEND_FAIL = "SEND_DATA_FAIL_ALARM"
    PARSE_LOG_FAIL = "PARSE_LOG_FAIL_ALARM"
"""


class TestRegistryConsistency:
    def test_tpu_without_native_sibling_flagged(self):
        src = """
        def register_all(registry):
            registry.register_processor("processor_parse_foo_tpu",
                                        ProcessorFoo)
        """
        findings = scan(src, RegistryConsistencyChecker(),
                        relpath="loongcollector_tpu/processor/__init__.py")
        assert checks_of(findings) == {"registry-consistency"}
        assert "no `processor_parse_foo_native` sibling" in \
            findings[0].message

    def test_paired_tiers_same_class_clean(self):
        src = """
        def register_all(registry):
            registry.register_processor("processor_parse_foo_native",
                                        ProcessorFoo)
            registry.register_processor("processor_parse_foo_tpu",
                                        ProcessorFoo)
        """
        assert scan(src, RegistryConsistencyChecker(),
                    relpath="loongcollector_tpu/processor/__init__.py") == []

    def test_tier_fork_flagged(self):
        src = """
        def register_all(registry):
            registry.register_processor("processor_parse_foo_native",
                                        ProcessorFooHost)
            registry.register_processor("processor_parse_foo_tpu",
                                        ProcessorFooDevice)
        """
        findings = scan(src, RegistryConsistencyChecker(),
                        relpath="loongcollector_tpu/processor/__init__.py")
        assert any("tier fork" in f.message for f in findings)

    def test_unknown_alarm_type_flagged(self):
        src = """
        from ..monitor.alarms import AlarmManager, AlarmType
        def fail(mgr):
            mgr.send_alarm(AlarmType.TOTALLY_BOGUS, "boom")
        """
        findings = scan(
            src, RegistryConsistencyChecker(),
            relpath="loongcollector_tpu/flusher/fx.py",
            extra_modules=[("loongcollector_tpu/monitor/alarms.py",
                            FAKE_ALARMS)])
        assert checks_of(findings) == {"registry-consistency"}
        assert "TOTALLY_BOGUS" in findings[0].message

    def test_known_alarm_type_clean(self):
        src = """
        from ..monitor.alarms import AlarmManager, AlarmType
        def ok(mgr):
            mgr.send_alarm(AlarmType.SEND_FAIL, "boom")
        """
        assert scan(
            src, RegistryConsistencyChecker(),
            relpath="loongcollector_tpu/flusher/fx.py",
            extra_modules=[("loongcollector_tpu/monitor/alarms.py",
                            FAKE_ALARMS)]) == []

    def test_raw_string_alarm_flagged(self):
        src = """
        def fail(mgr):
            mgr.send_alarm("SEND_DATA_FAIL_ALARM", "boom")
        """
        findings = scan(
            src, RegistryConsistencyChecker(),
            relpath="loongcollector_tpu/flusher/fx.py",
            extra_modules=[("loongcollector_tpu/monitor/alarms.py",
                            FAKE_ALARMS)])
        assert any("raw literal" in f.message for f in findings)


# ---------------------------------------------------------------------------
# 6. framework plumbing


class TestSwallowedFault:
    """swallowed-fault (ISSUE 2): broad except-pass/continue in flusher/
    and runner/ send paths eat injected chaos faults silently."""

    SCOPE = "loongcollector_tpu/flusher/fixture.py"

    def _scan(self, src, relpath=None):
        from loongcollector_tpu.analysis.checkers.swallowed_fault import \
            SwallowedFaultChecker
        return scan(src, SwallowedFaultChecker(),
                    relpath=relpath or self.SCOPE)

    def test_flags_broad_except_pass(self):
        findings = self._scan("""
            def deliver(payload):
                try:
                    sock.sendall(payload)
                except Exception:
                    pass
        """)
        assert checks_of(findings) == {"swallowed-fault"}
        assert findings[0].symbol == "deliver"

    def test_flags_bare_except_continue_in_loop(self):
        findings = self._scan("""
            def send_loop(queue):
                for item in queue:
                    try:
                        producer.send(item)
                    except:
                        continue
        """, relpath="loongcollector_tpu/runner/fixture.py")
        assert checks_of(findings) == {"swallowed-fault"}

    def test_flags_broad_tuple(self):
        findings = self._scan("""
            def send(x):
                try:
                    post(x)
                except (OSError, Exception):
                    pass
        """)
        assert checks_of(findings) == {"swallowed-fault"}

    def test_narrow_exception_ok(self):
        findings = self._scan("""
            def send(x):
                try:
                    post(x)
                except OSError:
                    pass
        """)
        assert findings == []

    def test_handler_that_logs_ok(self):
        findings = self._scan("""
            def send(x):
                try:
                    post(x)
                except Exception:
                    log.warning("send failed, will retry")
        """)
        assert findings == []

    def test_cleanup_only_try_body_exempt(self):
        findings = self._scan("""
            def stop(sock):
                try:
                    sock.close()
                except Exception:
                    pass
        """)
        assert findings == []

    def test_out_of_scope_paths_ignored(self):
        findings = self._scan("""
            def anything(x):
                try:
                    go(x)
                except Exception:
                    pass
        """, relpath="loongcollector_tpu/input/fixture.py")
        assert findings == []

    def test_inline_disable_suppresses(self):
        src = """
def send(x):
    try:
        probe_native(x)
    # loonglint: disable=swallowed-fault
    except Exception:
        pass
"""
        mod = ModuleInfo("/fx/" + self.SCOPE, self.SCOPE, src)
        from loongcollector_tpu.analysis.checkers.swallowed_fault import \
            SwallowedFaultChecker
        findings = list(SwallowedFaultChecker().check_module(mod))
        assert len(findings) == 1
        assert mod.suppressed(findings[0].line, findings[0].check)


class TestUnledgeredDrop:
    """unledgered-drop (ISSUE 8): event discards in runner//flusher//input//
    pipeline/queue/ must live in functions that touch the conservation
    ledger — the static half of the zero-loss audit."""

    SCOPE = "loongcollector_tpu/runner/fixture.py"

    def _scan(self, src, relpath=None):
        from loongcollector_tpu.analysis.checkers.unledgered_drop import \
            UnledgeredDropChecker
        return scan(src, UnledgeredDropChecker(),
                    relpath=relpath or self.SCOPE)

    def test_flags_logged_drop_without_ledger(self):
        findings = self._scan("""
            def dispatch(self, item):
                if item.flusher is None:
                    log.error("no sink wired; dropping payload")
                    self.sqm.remove_item(item)
                    return
        """)
        assert checks_of(findings) == {"unledgered-drop"}
        assert findings[0].symbol == "dispatch"
        assert "discard logged here" in findings[0].message

    def test_flags_drop_counter_without_ledger(self):
        findings = self._scan("""
            class Q:
                def push(self, group):
                    while len(self._items) > self._cap:
                        self._items.popleft()
                        self.total_dropped += 1
        """, relpath="loongcollector_tpu/pipeline/queue/fixture.py")
        assert checks_of(findings) == {"unledgered-drop"}
        assert "drop counter" in findings[0].message

    def test_flags_continue_after_broad_except(self):
        findings = self._scan("""
            def send_loop(self):
                for item in self._queue:
                    try:
                        self.deliver(item)
                    except Exception:
                        log.exception("send failed")
                        continue
        """, relpath="loongcollector_tpu/flusher/fixture.py")
        assert checks_of(findings) == {"unledgered-drop"}
        assert "abandons the current item" in findings[0].message

    def test_ledger_record_in_function_ok(self):
        findings = self._scan("""
            def dispatch(self, item):
                if item.flusher is None:
                    log.error("no sink wired; dropping payload")
                    ledger.record(self._pipeline, ledger.B_DROP,
                                  item.event_cnt, tag="no_sink")
                    self.sqm.remove_item(item)
                    return
        """)
        assert findings == []

    def test_self_ledger_helper_ok(self):
        findings = self._scan("""
            def send_loop(self):
                for item in self._queue:
                    try:
                        self.deliver(item)
                    except Exception:
                        self._ledger_drop(item, "send_failed")
                        log.exception("send failed, dropping item")
                        continue
        """, relpath="loongcollector_tpu/flusher/fixture.py")
        assert findings == []

    def test_ledger_is_on_guard_counts_as_touch(self):
        findings = self._scan("""
            def shed(self, group):
                if ledger.is_on():
                    _note(group)
                log.warning("queue full; shedding group")
        """)
        assert findings == []

    def test_narrow_except_continue_ok(self):
        findings = self._scan("""
            def send_loop(self):
                for item in self._queue:
                    try:
                        self.deliver(item)
                    except KeyError:
                        continue
        """, relpath="loongcollector_tpu/flusher/fixture.py")
        assert findings == []

    def test_return_after_except_outside_loop_ok(self):
        findings = self._scan("""
            def probe(self):
                try:
                    return self.fetch()
                except Exception:
                    return None
        """)
        assert findings == []

    def test_out_of_scope_paths_ignored(self):
        findings = self._scan("""
            def refresh(self):
                log.warning("stale sample dropped")
        """, relpath="loongcollector_tpu/monitor/fixture.py")
        assert findings == []

    def test_log_without_drop_words_ok(self):
        findings = self._scan("""
            def dispatch(self, item):
                log.warning("send slow, backing off")
        """)
        assert findings == []

    def test_inline_disable_suppresses(self):
        src = """
def evict(self):
    # cache eviction, no events ride the entry
    # loonglint: disable=unledgered-drop
    self.dropped_conns += 1
"""
        mod = ModuleInfo("/fx/" + self.SCOPE, self.SCOPE, src)
        from loongcollector_tpu.analysis.checkers.unledgered_drop import \
            UnledgeredDropChecker
        findings = list(UnledgeredDropChecker().check_module(mod))
        assert len(findings) == 1
        assert mod.suppressed(findings[0].line, findings[0].check)


class TestMetricNaming:
    """metric-naming (ISSUE 3): snake_case metric names, one exposition
    kind per name, and class-owned MetricsRecords must be released."""

    def _scan(self, src, relpath="loongcollector_tpu/runner/fixture.py",
              extra_modules=()):
        from loongcollector_tpu.analysis.checkers.metric_naming import \
            MetricNamingChecker
        return scan(src, MetricNamingChecker(), relpath=relpath,
                    extra_modules=extra_modules)

    # -- naming --------------------------------------------------------------

    def test_flags_non_snake_case_literal(self):
        findings = self._scan("""
            class R:
                def __init__(self):
                    self.metrics = MetricsRecord()
                    self.metrics.counter("camelCaseTotal")
                def stop(self):
                    self.metrics.mark_deleted()
        """)
        assert checks_of(findings) == {"metric-naming"}
        assert "snake_case" in findings[0].message

    def test_fstring_fragments_checked(self):
        findings = self._scan("""
            class R:
                def __init__(self, action):
                    self.metrics = MetricsRecord()
                    self.metrics.counter(f"faults_{action}_total")
                    self.metrics.counter(f"Bad-{action}_total")
                def stop(self):
                    self.metrics.mark_deleted()
        """)
        assert len(findings) == 1
        assert "'Bad-'" in findings[0].message

    def test_snake_case_names_pass(self):
        findings = self._scan("""
            class R:
                def __init__(self):
                    self.metrics = MetricsRecord()
                    self.metrics.counter("in_events_total")
                    self.metrics.gauge("state")
                    self.metrics.histogram("rtt_seconds")
                def stop(self):
                    self.metrics.mark_deleted()
        """)
        assert findings == []

    # -- kind uniqueness -----------------------------------------------------

    def test_flags_cross_module_kind_conflict(self):
        findings = self._scan("""
            class A:
                def __init__(self):
                    self.metrics = MetricsRecord()
                    self.metrics.counter("depth")
                def stop(self):
                    self.metrics.mark_deleted()
        """, extra_modules=[("loongcollector_tpu/flusher/fx2.py", """
            class B:
                def __init__(self):
                    self.metrics = MetricsRecord()
                    self.metrics.gauge("depth")
                def stop(self):
                    self.metrics.mark_deleted()
        """)])
        assert any("conflicting kinds counter/gauge" in f.message
                   for f in findings)

    def test_same_kind_everywhere_ok(self):
        findings = self._scan("""
            class A:
                def __init__(self):
                    self.m = MetricsRecord()
                    self.m.counter("in_events_total")
                def stop(self):
                    self.m.mark_deleted()
        """, extra_modules=[("loongcollector_tpu/flusher/fx2.py", """
            class B:
                def __init__(self):
                    self.m = MetricsRecord()
                    self.m.counter("in_events_total")
                def stop(self):
                    self.m.mark_deleted()
        """)])
        assert findings == []

    # -- ownership -----------------------------------------------------------

    def test_flags_leaked_record(self):
        """The pre-PR-3 SinkCircuitBreaker shape: a record created per
        construct, registered into WriteMetrics, never released."""
        findings = self._scan("""
            class Breaker:
                def __init__(self):
                    self.metrics = MetricsRecord(category="component")
                    self.opened = self.metrics.counter("opened_total")
        """)
        assert checks_of(findings) == {"metric-naming"}
        assert "never mark_deleted" in findings[0].message
        assert findings[0].symbol == "Breaker"

    def test_mark_deleted_in_class_ok(self):
        findings = self._scan("""
            class Runner:
                def __init__(self):
                    self.metrics = MetricsRecord()
                def stop(self):
                    self.metrics.mark_deleted()
        """)
        assert findings == []

    def test_escape_to_owner_ok(self):
        """The plugin-instance shape: the record is handed to an external
        owner (the pipeline's _metric_records) which releases it."""
        findings = self._scan("""
            class Instance:
                def __init__(self, plugin):
                    self.metrics = MetricsRecord()
                    plugin.metrics_record = self.metrics
        """)
        assert findings == []

    def test_append_escape_ok(self):
        findings = self._scan("""
            class Pipeline:
                def __init__(self):
                    self._records = []
                    self.metrics = MetricsRecord()
                    self._records.append(self.metrics)
        """)
        assert findings == []

    def test_module_level_record_exempt(self):
        findings = self._scan("""
            _rec = MetricsRecord(category="agent")
            _hist = _rec.histogram("wait_seconds")
        """)
        assert findings == []


class TestFramework:
    def test_allowlist_matching(self):
        from loongcollector_tpu.analysis.core import _allowed
        f = Finding("blocking-under-lock",
                    "loongcollector_tpu/flusher/pulsar.py", 170, 16,
                    "blocking call self.connect() while holding self._lock",
                    symbol="PulsarProducer.send")
        assert _allowed(f, [("flusher/pulsar.py", "blocking-under-lock",
                             "PulsarProducer.send")])
        assert not _allowed(f, [("flusher/pulsar.py", "acquire-release",
                                 "")])
        assert not _allowed(f, [("flusher/kafka.py",
                                 "blocking-under-lock", "")])

    def test_suppression_parsing(self):
        mod = ModuleInfo("/fx/x.py", "x.py",
                         "a = 1  # loonglint: disable=foo,bar\nb = 2\n")
        assert mod.suppressed(1, "foo")
        assert mod.suppressed(1, "bar")
        assert not mod.suppressed(1, "baz")
        assert not mod.suppressed(2, "foo")

    def test_findings_have_stable_json_shape(self):
        f = Finding("acquire-release", "p.py", 3, 1, "msg", symbol="f")
        assert f.to_dict() == {"check": "acquire-release", "path": "p.py",
                               "line": 3, "col": 1, "symbol": "f",
                               "message": "msg"}

    def test_allowlist_respects_path_boundaries(self):
        from loongcollector_tpu.analysis.core import _allowed
        f = Finding("blocking-under-lock",
                    "loongcollector_tpu/input/data.py", 1, 0, "msg")
        # `a.py` must not match `data.py` by suffix accident
        assert not _allowed(f, [("a.py", "blocking-under-lock", "")])
        assert _allowed(f, [("input/data.py", "blocking-under-lock", "")])
        assert _allowed(f, [("loongcollector_tpu/input/data.py",
                             "blocking-under-lock", "")])


# ---------------------------------------------------------------------------
# 9. hot-path-materialize fixtures (loongcolumn)


class TestHotPathMaterialize:
    def checker(self):
        from loongcollector_tpu.analysis.checkers.hot_path_materialize import \
            HotPathMaterializeChecker
        return HotPathMaterializeChecker()

    def test_events_read_in_serializer_flagged(self):
        src = """
        def serialize(groups):
            out = []
            for g in groups:
                for ev in g.events:
                    out.append(ev)
            return out
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/pipeline/serializer/fx.py")
        assert checks_of(fs) == {"hot-path-materialize"}
        assert any("materializes" in f.message for f in fs)

    def test_events_read_in_ops_flagged(self):
        src = """
        def pack(group):
            return [ev for ev in group.events]
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/ops/fx.py")
        assert checks_of(fs) == {"hot-path-materialize"}

    def test_private_events_and_columns_reads_are_clean(self):
        src = """
        def serialize(group):
            cols = group.columns
            if cols is not None and not group._events:
                return cols.offsets
            return None
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/pipeline/serializer/fx.py")
        assert fs == []

    def test_materialize_and_to_dict_calls_flagged(self):
        src = """
        def serialize(group):
            group.materialize()
            return [e.to_dict() for e in group._events]
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/pipeline/serializer/fx.py")
        assert len(fs) == 2

    def test_event_construction_in_ops_flagged(self):
        src = """
        from ..models.events import LogEvent

        def rebuild(rows):
            out = []
            for r in rows:
                ev = LogEvent(0)
                out.append(ev)
            return out
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/ops/fx.py")
        assert checks_of(fs) == {"hot-path-materialize"}

    def test_capable_plugin_body_construction_flagged(self):
        # OUTSIDE ops//serializer/: only columnar-capable class bodies
        # are in scope, and only calls/constructions — not .events reads
        src = """
        class ProcessorFx:
            name = "processor_fx"
            supports_columnar = True

            def process(self, group):
                ev = group.add_log_event(0)
                return ev
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/processor/fx.py")
        assert checks_of(fs) == {"hot-path-materialize"}

    def test_capable_plugin_row_fallback_events_read_is_clean(self):
        src = """
        class ProcessorFx:
            name = "processor_fx"
            supports_columnar = True

            def process(self, group):
                for ev in group.events:
                    pass
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/processor/fx.py")
        assert fs == []

    def test_non_capable_plugin_body_out_of_scope(self):
        src = """
        class ProcessorFx:
            name = "processor_fx"

            def process(self, group):
                ev = group.add_log_event(0)
                for e in group.events:
                    pass
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/processor/fx.py")
        assert fs == []

    def test_real_tree_fallbacks_are_suppressed_not_rewritten(self):
        # the canonical dict fallbacks carry justification comments; the
        # full-tree gate (TestTier1Gate) proves they are the ONLY hits
        import loongcollector_tpu.pipeline.serializer.event_dicts as ed
        import inspect
        src = inspect.getsource(ed)
        assert "loonglint: disable=hot-path-materialize" in src


# ---------------------------------------------------------------------------
# 10. per-row-parse fixtures (loongstruct)


class TestPerRowParse:
    @staticmethod
    def checker():
        from loongcollector_tpu.analysis.checkers.per_row_parse import \
            PerRowParseChecker
        return PerRowParseChecker()

    def test_json_loads_in_loop_flagged(self):
        src = """
        import json

        class ProcessorFx:
            supports_columnar = True

            def process(self, group):
                for i in idx:
                    obj = json.loads(rows[i])
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/processor/fx.py")
        assert checks_of(fs) == {"per-row-parse"}

    def test_fsm_split_in_loop_flagged(self):
        src = """
        class ProcessorFx:
            supports_columnar = True

            def process(self, group):
                while todo:
                    fields = _csv_fsm_split(todo.pop(), b",")
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/processor/fx.py")
        assert checks_of(fs) == {"per-row-parse"}

    def test_json_loads_in_comprehension_flagged(self):
        src = """
        import json

        class ProcessorFx:
            supports_columnar = True

            def process(self, group):
                objs = [json.loads(rows[i]) for i in idx]
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/processor/fx.py")
        assert checks_of(fs) == {"per-row-parse"}

    def test_bounded_probe_outside_loop_ok(self):
        src = """
        import json

        class ProcessorFx:
            supports_columnar = True

            def discover(self, row):
                return json.loads(row)
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/processor/fx.py") == []

    def test_non_columnar_class_out_of_scope(self):
        src = """
        import json

        class ProcessorFx:
            def process(self, group):
                for r in rows:
                    json.loads(r)
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/processor/fx.py") == []

    def test_real_tree_fallbacks_are_suppressed_with_justification(self):
        # the counted fallback tiers carry disable comments; the
        # full-tree gate (TestTier1Gate) proves they are the ONLY hits
        import inspect

        import loongcollector_tpu.processor.parse_delimiter as pd
        import loongcollector_tpu.processor.parse_json as pj
        assert "loonglint: disable=per-row-parse" in inspect.getsource(pj)
        assert "loonglint: disable=per-row-parse" in inspect.getsource(pd)


class TestUnboundedWindow:
    """unbounded-window (loongagg): dict window state in aggregator/ needs
    cap/TTL eviction wired to a counted metric — slow-OOM and silent-skew
    are both findings."""

    SCOPE = "loongcollector_tpu/aggregator/fixture.py"

    def _scan(self, src, relpath=None):
        from loongcollector_tpu.analysis.checkers.unbounded_window import \
            UnboundedWindowChecker
        return scan(src, UnboundedWindowChecker(),
                    relpath=relpath or self.SCOPE)

    def test_flags_dict_state_with_no_eviction(self):
        findings = self._scan("""
            class AggregatorLeaky:
                def __init__(self):
                    self._windows = {}

                def add(self, group):
                    self._windows.setdefault(key(group), []).append(group)
        """)
        assert checks_of(findings) == {"unbounded-window"}
        msg = findings[0].message
        assert "eviction site" in msg and "bound" in msg \
            and "counted metric" in msg
        assert findings[0].symbol == "AggregatorLeaky._windows"

    def test_flags_eviction_without_bound_or_counter(self):
        findings = self._scan("""
            class AggregatorHalf:
                def __init__(self):
                    self._state = {}

                def rotate(self, key):
                    self._state.pop(key, None)
        """)
        assert checks_of(findings) == {"unbounded-window"}
        msg = findings[0].message
        assert "eviction site" not in msg
        assert "bound comparison" in msg and "counted metric" in msg

    def test_clean_with_cap_eviction_and_counter(self):
        findings = self._scan("""
            class AggregatorBounded:
                def __init__(self, metrics):
                    self._windows = {}
                    self._m_evicted = metrics.counter("evict_total")

                def add(self, key, v):
                    if len(self._windows) >= self.max_keys:
                        self._windows.pop(next(iter(self._windows)))
                        self._m_evicted.add(1)
                    self._windows[key] = v
        """)
        assert findings == []

    def test_counter_registration_call_chain_is_evidence(self):
        findings = self._scan("""
            class AggregatorChained:
                def __init__(self):
                    self._buckets = {}

                def flush_timeout(self, now):
                    for key in list(self._buckets):
                        if now - self._buckets[key].born >= self.timeout_s:
                            del self._buckets[key]
                            _metrics().counter("timeout_total").add(1)
        """)
        assert findings == []

    def test_outside_aggregator_scope_is_ignored(self):
        findings = self._scan("""
            class Cache:
                def __init__(self):
                    self._entries = {}
        """, relpath="loongcollector_tpu/processor/fixture.py")
        assert findings == []

    def test_real_tree_aggregators_comply(self):
        # base.py (bucket cap + TTL + counted completions) and
        # metric_rollup.py (MaxKeys + counted eviction) both pass with
        # zero suppressions
        from loongcollector_tpu.analysis.checkers.unbounded_window import \
            UnboundedWindowChecker
        for rel in ("loongcollector_tpu/aggregator/base.py",
                    "loongcollector_tpu/aggregator/metric_rollup.py"):
            path = os.path.join(REPO, rel)
            with open(path) as f:
                mod = ModuleInfo(path, rel, f.read())
            assert list(UnboundedWindowChecker().check_module(mod)) == []

    def test_registered_in_tier1(self):
        from loongcollector_tpu.analysis.checkers import checker_names
        assert "unbounded-window" in checker_names()

    def test_unledgered_drop_scope_covers_aggregator(self):
        from loongcollector_tpu.analysis.checkers.unledgered_drop import \
            UnledgeredDropChecker
        findings = scan("""
            def add(self, group):
                for ev in group.events:
                    if ev.bad:
                        log.warning("dropping malformed metric row")
                        continue
        """, UnledgeredDropChecker(), relpath=self.SCOPE)
        assert checks_of(findings) == {"unledgered-drop"}


# ---------------------------------------------------------------------------
# 12. host-bounce fixtures (loongresident)


class TestHostBounce:
    def checker(self):
        from loongcollector_tpu.analysis.checkers.host_bounce import \
            HostBounceChecker
        return HostBounceChecker()

    def test_pull_between_two_dispatches_flagged(self):
        src = """
        def two_stage(rows, lengths):
            ok = np.asarray(index_kernel(rows, lengths))
            masks = np.asarray(ok)
            return np.asarray(match_kernel(rows, masks))
        """
        fs = scan(src, self.checker())
        assert checks_of(fs) == {"host-bounce"}
        assert any(f.line == 4 for f in fs)

    def test_pull_in_dispatch_loop_flagged(self):
        src = """
        def chunked(chunks):
            out = []
            for rows, lengths in chunks:
                out.append(np.asarray(scan_kernel(rows, lengths)))
            return out
        """
        fs = scan(src, self.checker())
        assert checks_of(fs) == {"host-bounce"}

    def test_pull_wrapping_first_dispatch_flagged(self):
        # the canonical straight-line bounce: materialise stage 1's
        # output on its own dispatch line, re-pack into stage 2
        src = """
        def two_stage(rows, lengths):
            a = np.asarray(index_kernel(rows, lengths))
            return match_kernel(rows, a)
        """
        fs = scan(src, self.checker())
        assert checks_of(fs) == {"host-bounce"}
        assert any(f.line == 3 for f in fs)

    def test_single_dispatch_then_materialise_clean(self):
        src = """
        def one_shot(rows, lengths):
            out = extract_kernel.donated_call(rows, lengths)
            return [np.asarray(o) for o in out]
        """
        assert scan(src, self.checker()) == []

    def test_donated_call_counts_as_dispatch(self):
        src = """
        def resident(rows, lengths):
            a = kern.donated_call(rows, lengths)
            host = np.asarray(a)
            return kern.donated_call(host, lengths)
        """
        fs = scan(src, self.checker())
        assert checks_of(fs) == {"host-bounce"}

    def test_future_result_between_dispatches_flagged(self):
        src = """
        def drain(self, chunks):
            for batch, fut in chunks:
                vals = fut.result()
                self.sub_kern(batch.rows, batch.lengths)
        """
        fs = scan(src, self.checker())
        assert checks_of(fs) == {"host-bounce"}

    def test_outside_scope_ignored(self):
        src = """
        def two_stage(rows, lengths):
            a = np.asarray(index_kernel(rows, lengths))
            return np.asarray(match_kernel(rows, a))
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/runner/fx.py") == []

    def test_processor_scope_requires_columnar_capable(self):
        body = """
        class ProcessorFx:
            supports_columnar = True

            def process(self, rows, lengths):
                a = np.asarray(self._dfa_kernel(rows, lengths))
                b = np.asarray(a)
                return self._seg_kernel(rows, b)
        """
        fs = scan(body, self.checker(),
                  relpath="loongcollector_tpu/processor/fx.py")
        assert checks_of(fs) == {"host-bounce"}
        plain = body.replace("supports_columnar = True",
                             "supports_columnar = False")
        assert scan(plain, self.checker(),
                    relpath="loongcollector_tpu/processor/fx.py") == []

    def test_suppression_escapes(self):
        src = textwrap.dedent("""
        def demoted(rows, lengths):
            # loonglint: disable=host-bounce
            a = np.asarray(index_kernel(rows, lengths))
            return match_kernel(rows, a)
        """)
        mod = ModuleInfo("/fx/loongcollector_tpu/ops/fixture.py",
                         "loongcollector_tpu/ops/fixture.py", src)
        fs = list(self.checker().check_module(mod))
        # the bounce IS found (raw), and the comment-line suppression
        # covers it at the runner layer — the designed-fallback escape
        assert fs
        assert all(mod.suppressed(f.line, "host-bounce") for f in fs)

    def test_bare_asarray_helper_not_a_pull(self):
        src = """
        def two_stage(rows, lengths):
            a = index_kernel(rows, lengths)
            b = asarray(a)
            return match_kernel(rows, b)
        """
        assert scan(src, self.checker()) == []

    def test_registered_in_tier1(self):
        from loongcollector_tpu.analysis.checkers import checker_names
        assert "host-bounce" in checker_names()


# ---------------------------------------------------------------------------
# 13. reload-unsafe fixtures (loongtenant)


class TestReloadUnsafe:
    def checker(self):
        from loongcollector_tpu.analysis.checkers.reload_unsafe import \
            ReloadUnsafeChecker
        return ReloadUnsafeChecker()

    def test_register_without_unregister_flagged(self):
        src = """
        class LeakyHook:
            def init(self, cfg, ctx):
                TimeoutFlushManager.instance().register(self._hook)
                return True

            def stop(self, removing=False):
                pass
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/pipeline/fixture.py")
        assert checks_of(fs) == {"reload-unsafe"}
        assert any("unregister" in f.message for f in fs)

    def test_register_with_unregister_clean(self):
        src = """
        class PairedHook:
            def init(self, cfg, ctx):
                TimeoutFlushManager.instance().register(self._hook)
                return True

            def release(self):
                TimeoutFlushManager.instance().unregister(self._hook)
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/pipeline/fixture.py") == []

    def test_registry_class_itself_exempt(self):
        src = """
        class InputRunnerRegistry:
            def register(self, name, job):
                self._jobs[name] = job

            def wire(self, name, job):
                self._inner.register(name, job)
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/runner/fixture.py") == []

    def test_self_held_future_without_settle_flagged(self):
        src = """
        class LeakyDispatch:
            def dispatch(self, kernel, args, nbytes):
                self._fut = self._plane.submit(kernel, args, nbytes)

            def stop(self):
                self._fut = None
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/ops/fixture.py")
        assert checks_of(fs) == {"reload-unsafe"}
        assert any("strands plane budget" in f.message for f in fs)

    def test_self_held_future_with_result_clean(self):
        src = """
        class SettlingDispatch:
            def dispatch(self, kernel, args, nbytes):
                self._fut = self._plane.submit(kernel, args, nbytes)

            def materialise(self):
                return self._fut.result()
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/ops/fixture.py") == []

    def test_container_held_future_via_local_flagged(self):
        src = """
        class RingLeak:
            def dispatch(self, kernel, args, nbytes):
                fut = self._plane.submit(kernel, args, nbytes)
                self._pending.append((fut, nbytes))
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/ops/fixture.py")
        assert checks_of(fs) == {"reload-unsafe"}

    def test_container_held_lease_with_release_clean(self):
        src = """
        class RingHolder:
            def pack(self, ring, geometry):
                slot = ring.lease(geometry)
                self._slots.append(slot)

            def advance(self):
                self._slots.pop(0).release()
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/ops/fixture.py") == []

    def test_subscript_held_future_flagged(self):
        src = """
        class SlotLeak:
            def dispatch(self, key, kernel, args, nbytes):
                fut = self._plane.submit(kernel, args, nbytes)
                self._by_key[key] = fut
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/ops/fixture.py")
        assert checks_of(fs) == {"reload-unsafe"}

    def test_nested_closure_hold_reported_once(self):
        # the closure is reachable from the method walk AND as its own
        # FunctionDef — the finding must not duplicate
        src = """
        class ClosureLeak:
            def dispatch(self, chunks):
                def _one(c):
                    fut = self._plane.submit(c.kern, c.args, c.nbytes)
                    self._pending.append(fut)
                for c in chunks:
                    _one(c)
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/ops/fixture.py")
        assert len(fs) == 1, [f.format() for f in fs]

    def test_inner_class_sites_not_charged_to_outer(self):
        # the inner class's unbalanced register() is ITS finding alone
        src = """
        class Outer:
            def stop(self):
                pass

            class Inner:
                def init(self):
                    TimeoutFlushManager.instance().register(self._hook)
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/pipeline/fixture.py")
        assert len(fs) == 1
        assert fs[0].symbol == "Inner"

    def test_direct_subscript_store_of_hold_call_flagged(self):
        # no intermediate local: the hold call stored straight into a
        # self container must count too
        src = """
        class SlotLeakDirect:
            def dispatch(self, key, kernel, args, nbytes):
                self._by_key[key] = self._plane.submit(kernel, args,
                                                       nbytes)
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/ops/fixture.py")
        assert checks_of(fs) == {"reload-unsafe"}

    def test_private_record_with_stop_no_retire_flagged(self):
        src = """
        class LeakyComponent:
            def __init__(self):
                self._metrics = MetricsRecord(category="component",
                                              labels={})

            def stop(self):
                self._running = False
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/runner/fixture.py")
        assert checks_of(fs) == {"reload-unsafe"}
        assert any("mark_deleted" in f.message for f in fs)

    def test_private_record_with_retire_clean(self):
        src = """
        class RetiringComponent:
            def __init__(self):
                self._metrics = MetricsRecord(category="component",
                                              labels={})

            def stop(self):
                self._metrics.mark_deleted()
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/runner/fixture.py") == []

    def test_public_record_escapes_to_owner_clean(self):
        # public self.metrics may escape to an owning pipeline, which
        # retires it (the ProcessorInstance pattern) — metric-naming's
        # ownership rule covers those; reload-unsafe stays silent
        src = """
        class PluginWrapper:
            def __init__(self):
                self.metrics = MetricsRecord(category="plugin", labels={})

            def stop(self, removing=False):
                pass
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/pipeline/fixture.py") == []

    def test_outside_scope_ignored(self):
        src = """
        class Elsewhere:
            def init(self):
                TimeoutFlushManager.instance().register(self._hook)
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/monitor/fixture.py") == []

    def test_suppression_escapes(self):
        src = textwrap.dedent("""
        class Singleton:
            def init(self):
                # loonglint: disable=reload-unsafe
                TimeoutFlushManager.instance().register(self._hook)
        """)
        mod = ModuleInfo("/fx/loongcollector_tpu/pipeline/fixture.py",
                         "loongcollector_tpu/pipeline/fixture.py", src)
        fs = list(self.checker().check_module(mod))
        assert fs
        assert all(mod.suppressed(f.line, "reload-unsafe") for f in fs)

    def test_real_tree_clean(self):
        from loongcollector_tpu.analysis.core import run_analysis
        result = run_analysis(checkers=[self.checker()])
        assert result.findings == [], [
            f.format() for f in result.findings]

    def test_registered_in_tier1(self):
        from loongcollector_tpu.analysis.checkers import checker_names
        assert "reload-unsafe" in checker_names()


# ---------------------------------------------------------------------------
# 15. stamp-propagation fixtures (loongslo)


class TestStampPropagation:
    def checker(self):
        from loongcollector_tpu.analysis.checkers.stamp_propagation import \
            StampPropagationChecker
        return StampPropagationChecker()

    def test_derived_group_without_carrier_flagged(self):
        # the pre-fix udpserver._dispatch shape: re-routed events re-emerge
        # in a fresh group over the SAME arena, stamp left behind
        src = """
        class Dispatcher:
            def _dispatch(self, group):
                for key, events in self._route(group):
                    out = PipelineEventGroup(group.source_buffer)
                    out.events.extend(events)
                    self._sinks[key](out)
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/input/fixture.py")
        assert checks_of(fs) == {"stamp-propagation"}
        assert any("ingest stamp is lost" in f.message for f in fs)

    def test_copy_meta_to_clean(self):
        src = """
        class Dispatcher:
            def _dispatch(self, group):
                for key, events in self._route(group):
                    out = PipelineEventGroup(group.source_buffer)
                    group.copy_meta_to(out)
                    out.events.extend(events)
                    self._sinks[key](out)
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/input/fixture.py") == []

    def test_group_meta_helper_clean(self):
        # the aggregator-family idiom: a _group_meta helper copies tags +
        # metadata onto every fresh bucket group
        src = """
        class Aggregator:
            def add(self, group):
                for ev in group.events:
                    out = PipelineEventGroup(group.source_buffer)
                    self._group_meta(out, self._key(group, ev), group)
                    out.events.append(ev)
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/aggregator/fixture.py") == []

    def test_explicit_restamp_clean(self):
        src = """
        class Splitter:
            def split(self, group):
                out = PipelineEventGroup(group.source_buffer)
                v = group.get_metadata(EventGroupMetaKey.INGEST_NS)
                if v is not None:
                    out.set_metadata(EventGroupMetaKey.INGEST_NS, str(v))
                return out
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/processor/fixture.py") == []

    def test_slo_stamp_call_clean(self):
        # a site that mints its own stamp (rollup emit at window close)
        src = """
        class Rollup:
            def emit(self, group):
                out = PipelineEventGroup(group.source_buffer)
                slo.ensure_stamp(self._pipeline, out)
                return out
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/aggregator/fixture.py") == []

    def test_fresh_arena_not_derived(self):
        # constructing over a NEW SourceBuffer is a fresh admission — the
        # ingest hook stamps it; this checker must stay silent
        src = """
        class Input:
            def _make_group(self, data):
                sb = SourceBuffer(len(data) + 64)
                group = PipelineEventGroup(sb)
                group.events.append(self._parse(data))
                return group
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/input/fixture.py") == []

    def test_bare_construction_not_derived(self):
        src = """
        def make_group():
            return PipelineEventGroup()
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/input/fixture.py") == []

    def test_nested_function_owns_its_site(self):
        # the closure is its own derivation scope: a carrier in the OUTER
        # function must not excuse the inner bare construction
        src = """
        class Router:
            def route(self, group):
                def _make():
                    return PipelineEventGroup(group.source_buffer)
                keep = PipelineEventGroup(group.source_buffer)
                group.copy_meta_to(keep)
                return _make(), keep
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/input/fixture.py")
        assert len(fs) == 1, [f.format() for f in fs]
        assert fs[0].symbol.endswith("_make")

    def test_suppression_escapes(self):
        src = textwrap.dedent("""
        class DebugProbe:
            def sample(self, group):
                # loonglint: disable=stamp-propagation
                return PipelineEventGroup(group.source_buffer)
        """)
        mod = ModuleInfo("/fx/loongcollector_tpu/input/fixture.py",
                         "loongcollector_tpu/input/fixture.py", src)
        fs = list(self.checker().check_module(mod))
        assert fs
        assert all(mod.suppressed(f.line, "stamp-propagation") for f in fs)

    def test_real_tree_clean(self):
        from loongcollector_tpu.analysis.core import run_analysis
        result = run_analysis(checkers=[self.checker()])
        assert result.findings == [], [
            f.format() for f in result.findings]

    def test_registered_in_tier1(self):
        from loongcollector_tpu.analysis.checkers import checker_names
        assert "stamp-propagation" in checker_names()


# ---------------------------------------------------------------------------
# 16. unwatched-jit fixtures (loongxprof)


class TestUnwatchedJit:
    def checker(self):
        from loongcollector_tpu.analysis.checkers.unwatched_jit import \
            UnwatchedJitChecker
        return UnwatchedJitChecker()

    def test_raw_jit_call_site_flagged(self):
        # the pre-loongxprof ExtractKernel shape: a raw jax.jit whose
        # compile cache no counter and no storm alarm can see
        src = """
        class ExtractKernel:
            def __init__(self, program):
                self._fn = jax.jit(build_extract_fn(program))
        """
        fs = scan(src, self.checker())
        assert checks_of(fs) == {"unwatched-jit"}
        assert len(fs) == 1

    def test_bare_decorator_flagged(self):
        src = """
        @jax.jit
        def step(x):
            return x + 1
        """
        fs = scan(src, self.checker())
        assert len(fs) == 1
        assert fs[0].symbol == "step"

    def test_partial_decorator_flagged(self):
        # the pre-fix field_extract_pallas shape
        src = """
        @functools.partial(jax.jit, static_argnums=())
        def extract(rows, lengths):
            return rows
        """
        fs = scan(src, self.checker())
        assert len(fs) == 1

    def test_watched_jit_is_clean(self):
        src = """
        from .compile_watch import watched_jit

        class ExtractKernel:
            def __init__(self, program):
                self._fn = watched_jit(build_extract_fn(program), "extract")
        """
        assert scan(src, self.checker()) == []

    def test_host_layer_out_of_scope(self):
        # runner/-layer code may jit freely — compile watching targets the
        # kernel planes under ops/ and parallel/
        src = """
        def probe():
            return jax.jit(lambda x: x)(1)
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/runner/fixture.py") == []

    def test_compile_watch_itself_exempt(self):
        src = """
        def watched_jit(fn, family, **jit_kwargs):
            return WatchedFn(jax.jit(fn, **jit_kwargs), family)
        """
        assert scan(src, self.checker(),
                    relpath="loongcollector_tpu/ops/compile_watch.py") == []

    def test_parallel_layer_in_scope(self):
        src = """
        class ShardedParsePlane:
            def __init__(self, fn):
                self._fn = jax.jit(fn)
        """
        fs = scan(src, self.checker(),
                  relpath="loongcollector_tpu/parallel/fixture.py")
        assert len(fs) == 1

    def test_suppression_escapes(self):
        # a one-shot capability probe is a legitimate unwatched jit when
        # it carries a justification (engine.py's dispatch probe)
        src = textwrap.dedent("""
        def _run_dispatch_probe():
            # probe compiles once per process; not a recurring cost
            # loonglint: disable=unwatched-jit
            g = jax.jit(lambda r: r.sum())
            return g
        """)
        mod = ModuleInfo("/fx/loongcollector_tpu/ops/fixture.py",
                         "loongcollector_tpu/ops/fixture.py", src)
        fs = list(self.checker().check_module(mod))
        assert fs
        assert all(mod.suppressed(f.line, "unwatched-jit") for f in fs)

    def test_real_tree_clean(self):
        from loongcollector_tpu.analysis.core import run_analysis
        result = run_analysis(checkers=[self.checker()])
        assert result.findings == [], [
            f.format() for f in result.findings]

    def test_registered_in_tier1(self):
        from loongcollector_tpu.analysis.checkers import checker_names
        assert "unwatched-jit" in checker_names()

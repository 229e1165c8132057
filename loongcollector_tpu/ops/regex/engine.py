"""Unified regex engine: tier dispatch + batch orchestration.

The single entry point processors use.  Given a pattern, picks the execution
tier (segment kernel / DFA kernel / CPU `re`), routes small batches to the
host walker, and returns arena-absolute capture spans so downstream stays
zero-copy (SURVEY.md §7 step 4: spans must index the ORIGINAL arena).
Device chunks ride one `DeviceStream` window (ops/device_stream.py), which
owns geometry bucketing, row packing, the in-flight ring and its releases;
`PendingParse` supplies the kernel, the recovery and the span assembly.

Oversize events (> largest length bucket) and CPU-tier patterns run through
the Python `re` fallback with identical semantics — the reference's
"route unsupported patterns to CPU" contract.
"""

from __future__ import annotations

import os
import re
import time
from typing import Optional, Tuple

import numpy as np

import threading

from ... import chaos
from .. import chip_lanes
from ..chip_lanes import ChipLaneFault, lane_gated
from ..device_batch import (LENGTH_BUCKETS, MAX_BATCH, pack_rows,
                            pick_length_bucket)
from ..kernels.dfa_scan import DFAMatchKernel
from ..kernels.field_extract import ExtractKernel, MatchKernel
from .dfa import DFAUnsupported, compile_dfa
from .program import (Alt, Optional_, PatternTier, Tier1Unsupported,
                      compile_tier1)


def _pallas_enabled() -> Optional[bool]:
    """LOONG_PALLAS=1 forces the fused Pallas path, =0 forces the XLA
    path; unset → auto (Pallas on real TPU, XLA elsewhere — the Pallas
    interpreter is a debugging tool, not a fast CPU path)."""
    env = os.environ.get("LOONG_PALLAS")
    if env is not None:
        return env == "1"
    return None


_host_backend_cached: Optional[bool] = None

# ---------------------------------------------------------------------------
# Latency-aware device routing.
#
# A device dispatch costs a fixed round trip before any bytes are parsed,
# while the native C++ walker starts instantly at a few hundred MB/s.  The
# crossover is
#     min_bytes = latency / (1/host_throughput - 1/device_bandwidth)
# — below it the host tier finishes before the device call would even
# return.  Latency AND effective host<->device bandwidth are MEASURED once
# per process with a two-size payload probe; the numbers are logged once
# and served in /debug/status (``device.routing``) so the decision is never
# invisible.  A probe that raises, raises: a local chip that cannot run a
# row reduction cannot run the parse either.  LOONG_DEVICE_MIN_BYTES
# overrides.

_HOST_WALKER_BPS = 300e6          # conservative native-walker throughput
_NEVER_DEVICE = 1 << 60           # crossover when the device cannot win
_dispatch_probe_lock = threading.Lock()
_device_min_bytes_cached: Optional[int] = None
_dispatch_probe_doc: Optional[dict] = None

# rows that did NOT go to the device + counted device-kernel fallbacks,
# cumulative for the life of the process (the benchmark reads them through
# /debug/status: ``device_row_share`` and the ``kernel_fallbacks`` check of
# ``correct``; chip_smoke.py reads the same).  Rows that did go are the
# batch ring's ``real_rows`` (``streaming.ring``); lane respills are the
# lanes' own ``respilled_events`` (``mesh.lanes``).
_route_lock = threading.Lock()
_route_rows = {"host_walker": 0, "cpu_re": 0}
_kernel_first_choice: Optional[str] = None
_kernel_fallbacks = 0


def _note_rows(tier: str, n: int) -> None:
    with _route_lock:
        _route_rows[tier] += n


def _note_kernel_fallback(msg: str, *args) -> None:
    """A REAL device-kernel failure answered by another path: counted
    (``device.routing.kernel_fallbacks_total`` — the benchmark's ``correct``
    and chip_smoke.py fail on a non-zero count) and logged with its
    traceback.  Production fault handling, never silent."""
    global _kernel_fallbacks
    with _route_lock:
        _kernel_fallbacks += 1
    from ...utils.logger import get_logger
    get_logger("regex").exception(msg, *args)


def _rerun(kern, c) -> tuple:
    """A chunk's synchronous recovery re-run on ``kern``: the slot still
    holds the packed rows."""
    # the designed exception path
    # loonglint: disable=host-bounce
    return tuple(np.asarray(a) for a in kern(c.batch.rows, c.batch.lengths))


def _note_first_choice(kern) -> None:
    """Record the device kernel the engine selected before any runtime
    fallback, by the ``watched_jit`` family it compiles under."""
    global _kernel_first_choice
    if _kernel_first_choice is None:
        base = getattr(kern, "base", kern)      # lane-placed wrapper
        _kernel_first_choice = getattr(base, "family", type(base).__name__)


def routing_status() -> dict:
    """The routing decision document (/debug/status ``device.routing``):
    which switch forced the tier (if any), the probe's measurements, rows
    kept on the host per tier, the device kernel family chosen first and
    how often a dispatch fell back off it."""
    forced = [f"{k}={os.environ[k]}" for k in
              ("LOONG_NATIVE_T1", "LOONG_PALLAS", "LOONG_DEVICE_MIN_BYTES")
              if os.environ.get(k) is not None]
    with _route_lock:
        return {"forced": forced,
                "probe": _dispatch_probe_doc,
                "rows": dict(_route_rows),
                "kernel_first_choice": _kernel_first_choice,
                "kernel_fallbacks_total": _kernel_fallbacks}


def _device_min_bytes() -> int:
    global _device_min_bytes_cached, _dispatch_probe_doc
    if _device_min_bytes_cached is not None:
        return _device_min_bytes_cached
    env = os.environ.get("LOONG_DEVICE_MIN_BYTES")
    if env is not None:
        _device_min_bytes_cached = int(env)
        return _device_min_bytes_cached
    with _dispatch_probe_lock:
        if _device_min_bytes_cached is not None:
            return _device_min_bytes_cached
        _dispatch_probe_doc = _run_dispatch_probe()
        _device_min_bytes_cached = _dispatch_probe_doc["crossover_bytes"]
    return _device_min_bytes_cached


def _run_dispatch_probe() -> dict:
    """Measure the device round trip and derive the routing crossover
    (``latency_s``, ``bandwidth_Bps``, ``crossover_bytes``).

    The probe mimics the REAL parse path: host-resident numpy rows in, a
    row-reduction out, result materialised back to the host.  (A
    `jnp.zeros` input lives on-device already and hides the transfer.)
    Two payload sizes fit the affine cost t(n) = lat + n/bw, separating
    fixed dispatch latency from effective host<->device bandwidth.  Each
    size is read five times and the LEAST reading kept: a round trip has a
    floor and no ceiling, a reading above the floor is somebody else's work
    (the probe runs while the agent's threads start), and the one decision
    it feeds holds for the whole process — the middle of three readings
    put the crossover anywhere from 380 to 590 KB on one chip host, on
    either side of the 512 KiB group (PERF.md section 7, PR 31)."""
    import jax
    import jax.numpy as jnp
    # not a kernel family: a once-per-process latency probe whose
    # compile cost IS part of what it measures — compile_watch
    # accounting would pollute the families it exists to audit
    # loonglint: disable=unwatched-jit
    g = jax.jit(lambda r: r.astype(jnp.int32).sum(axis=1))
    sizes = [(2048, 128), (8192, 512)]      # 256 KB, 4 MB
    times = []
    for B, L in sizes:
        rows = np.zeros((B, L), np.uint8)
        np.asarray(g(rows))                 # compile + warm path
        samples = []
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(g(rows))
            samples.append(time.perf_counter() - t0)
        times.append(min(samples))
    n0, n1 = (B * L for B, L in sizes)
    t0_, t1_ = times
    bw = (n1 - n0) / max(t1_ - t0_, 1e-9)
    lat = max(t0_ - n0 / bw, 1e-6)
    if bw <= _HOST_WALKER_BPS * 1.1:
        # effective device bandwidth can't beat the host walker at ANY
        # size: never route host-resident batches to the device
        crossover = _NEVER_DEVICE
    else:
        # clamp: one noisy latency sample must not pin multi-hundred-MB
        # batches to the host for the whole process
        crossover = max(32 * 1024, min(
            int(lat / (1.0 / _HOST_WALKER_BPS - 1.0 / bw)),
            128 * 1024 * 1024))
    from ...utils.logger import get_logger
    get_logger("regex").info(
        "device routing probe: latency %.3f ms, host<->device bandwidth "
        "%.1f MB/s, host walker assumed %.0f MB/s -> batches under %s go "
        "to the host walker", lat * 1e3, bw / 1e6, _HOST_WALKER_BPS / 1e6,
        "ANY size (device never wins)" if crossover == _NEVER_DEVICE
        else f"{crossover} bytes")
    return {"latency_s": round(lat, 6), "bandwidth_Bps": round(bw),
            "host_walker_assumed_Bps": round(_HOST_WALKER_BPS),
            "crossover_bytes": crossover}


def _native_host_mode() -> bool:
    """True when Tier-1 programs should run on the native C++ walker:
    the backend is CPU (an explicit pin — tests and CPU drives), where
    XLA's emulation of the masked-reduction kernel is ~10× slower than a
    scalar walk.  LOONG_NATIVE_T1=1 forces it, =0 disables it."""
    env = os.environ.get("LOONG_NATIVE_T1")
    if env is not None:
        return env == "1"
    if os.environ.get("LOONG_PALLAS") is not None:
        return False  # explicit device-kernel force wins over host auto
    global _host_backend_cached
    if _host_backend_cached is None:
        import jax
        _host_backend_cached = jax.default_backend() == "cpu"
    return _host_backend_cached


def routes_to_host(lengths: np.ndarray, has_host_tier) -> bool:
    """The routing rule of every SEGMENT-tier dispatch, applied to the byte
    sum of the rows that would cross: a CPU backend keeps them on the host;
    on an accelerator small batches still lose to the fixed dispatch round
    trip and go to the host tier (``has_host_tier()``: is there one) where
    the sum is under the crossover the probe measured.  Explicit
    LOONG_PALLAS / LOONG_NATIVE_T1 forces win."""
    if _native_host_mode():
        return True
    if _pallas_enabled() is None \
            and os.environ.get("LOONG_NATIVE_T1") != "0":
        return bool(has_host_tier()) \
            and int(lengths.sum()) < _device_min_bytes()
    return False


def pallas_by_default() -> bool:
    """The single-device kernel choice: an explicit LOONG_PALLAS force,
    else Pallas on a real TPU and XLA elsewhere."""
    forced = _pallas_enabled()
    if forced is not None:
        return forced
    import jax
    return jax.default_backend() == "tpu"


def _chunks(idx, size: int):
    """``idx`` in pieces of at most ``size`` rows.  An int stands for "rows
    0 .. idx-1, in order" and yields slices: a chunk of them is a view to
    pack from and to deliver into, with no gather and no scatter."""
    if isinstance(idx, int):
        for i in range(0, idx, size):
            yield slice(i, min(i + size, idx))
        return
    for i in range(0, len(idx), size):
        yield idx[i : i + size]


def _n_rows(chunk) -> int:
    return chunk.stop - chunk.start if isinstance(chunk, slice) \
        else len(chunk)


class BatchParseResult:
    """ok: bool [N]; cap_off/cap_len: int32 [N, C] arena-absolute spans
    (len -1 ⇒ no capture / failed parse)."""

    __slots__ = ("ok", "cap_off", "cap_len")

    def __init__(self, ok, cap_off, cap_len):
        self.ok = ok
        self.cap_off = cap_off
        self.cap_len = cap_len


from collections import OrderedDict

_engine_cache: "OrderedDict" = OrderedDict()
_engine_cache_lock = threading.Lock()
_ENGINE_CACHE_MAX = 512


def clear_engine_cache() -> None:
    """Drop every cached engine.  Mesh width (``LOONG_MESH_CHIPS``), lane
    routing and backend forces are resolved once per engine — tests clear
    the cache after changing them so the next ``get_engine`` re-resolves
    against the new environment."""
    with _engine_cache_lock:
        _engine_cache.clear()


def get_engine(pattern: str,
               force_tier: Optional[PatternTier] = None) -> "RegexEngine":
    """Process-wide engine cache: pipeline reloads and same-pattern plugins
    reuse compiled kernels instead of re-jitting (compilation is the
    dominant cost of a pipeline swap)."""
    if isinstance(pattern, bytes):
        pattern = pattern.decode("latin-1")
    key = (pattern, force_tier)
    with _engine_cache_lock:
        eng = _engine_cache.get(key)
        if eng is not None:
            _engine_cache.move_to_end(key)  # LRU touch
            return eng
    # compile outside the lock (jit can take seconds); races build the same
    # engine twice at worst
    eng = RegexEngine(pattern, force_tier)
    eng.warm_host()
    with _engine_cache_lock:
        _engine_cache[key] = eng
        while len(_engine_cache) > _ENGINE_CACHE_MAX:
            _engine_cache.popitem(last=False)  # evict least-recently used
    return eng


class _LanePlacedKernel:
    """A single-device kernel pinned to one chip lane (loongmesh): inputs
    are device_put onto the lane's chip, so the jitted step executes on
    that chip's stream — distinct workers drive distinct chips with no
    collectives on the batch path.  Its own ``device_put`` of each input:
    a placed kernel keeps ``(rows, lengths)`` → tuple and offers no
    packed entry (ops/packed_io.py)."""

    __slots__ = ("base", "lane")

    def __init__(self, base, lane):
        self.base = base
        self.lane = lane

    def _place(self, rows, lengths):
        import jax
        return (jax.device_put(rows, self.lane.device),
                jax.device_put(lengths, self.lane.device))

    def __call__(self, rows, lengths):
        rows_d, lens_d = self._place(rows, lengths)
        return self.base(rows_d, lens_d)


class RegexEngine:
    def __init__(self, pattern: str, force_tier: Optional[PatternTier] = None):
        if isinstance(pattern, bytes):
            pattern = pattern.decode("latin-1")
        self.pattern = pattern
        self._re = re.compile(pattern.encode("latin-1"))
        self.num_caps = self._re.groups
        self.group_names = {v - 1: k for k, v in self._re.groupindex.items()}
        self._segment_kernel: Optional[ExtractKernel] = None
        self._pallas_kernel = None          # built lazily on first use
        self._use_pallas: Optional[bool] = None
        self._sharded = None                # None=unresolved, False=off
        self._lane_kernels = {}             # chip index -> _LanePlacedKernel
        self._match_kernels = {}            # None / chip index -> match gate
        self._native_exec = None            # host C++ walker, built lazily
        self._native_tried = False
        self._dfa_kernel: Optional[DFAMatchKernel] = None
        self._fused_single = None           # loongfuse host exec, lazy
        self._fused_tried = False
        self._dfa_scanner = None            # fused host scanner (DFA tier)
        self.tier = PatternTier.CPU
        if force_tier in (None, PatternTier.SEGMENT):
            try:
                self._segment_kernel = ExtractKernel(compile_tier1(pattern))
                self.tier = PatternTier.SEGMENT
            except Tier1Unsupported:
                pass
        if self.tier is PatternTier.CPU and force_tier in (None, PatternTier.DFA):
            try:
                self._dfa_kernel = DFAMatchKernel(compile_dfa(pattern))
                self.tier = PatternTier.DFA
            except DFAUnsupported:
                pass
        if force_tier is not None and self.tier is not force_tier \
                and force_tier is not PatternTier.CPU:
            raise ValueError(f"pattern {pattern!r} cannot run at {force_tier}")
        # demotion observability (loongfuse satellite): a pattern falling
        # off the device tier used to be SILENT — a TPU collapse like
        # multiline-java's 1.6 MB/s was invisible until a bench run
        if force_tier is None:
            from .fuse import note_demotion
            if self.tier is PatternTier.CPU:
                note_demotion(pattern,
                              "no device tier (Tier-1 and DFA compile "
                              "both refused)")
            elif self.tier is PatternTier.DFA and self.num_caps > 0:
                note_demotion(pattern,
                              "capture-needing Tier-2 (device gates the "
                              "match; captures extract on host)")

    # ------------------------------------------------------------------

    def set_device_kernel_override(self, kern) -> None:
        """Test/diagnostic hook: route this engine's device dispatches
        through `kern` (e.g. a LatencyInjectedKernel modelling a slow
        device).  None restores normal selection."""
        self._kernel_override = kern

    def _maybe_sharded(self):
        """Multi-chip engine mode (SURVEY §2.7): when enabled and more than
        one device is attached, SEGMENT-tier dispatches run through
        ShardedParsePlane — the batch dimension shards over the ICI mesh,
        per-chip extraction + psum'd telemetry.  The plane rides the same
        async DevicePlane budget as single-chip dispatch, so watermark
        back-pressure is unchanged.  LOONG_SHARDED=1 forces, =0 disables;
        default auto (on when >1 device)."""
        if self._sharded is not None:
            return self._sharded or None
        env = os.environ.get("LOONG_SHARDED", "").strip()
        if env == "0" or self._segment_kernel is None:
            self._sharded = False
            return None
        if env != "1" and _pallas_enabled() is not None:
            # an explicit LOONG_PALLAS force pins the single-device kernel
            # choice; only an explicit LOONG_SHARDED=1 outranks it
            self._sharded = False
            return None
        import jax
        if len(jax.devices()) <= 1 and env != "1":
            self._sharded = False
            return None
        # a mesh that cannot be built on attached devices raises: a
        # multi-chip host quietly running on one chip hides the device
        from ...parallel.mesh import ShardedKernel
        self._sharded = ShardedKernel(self._segment_kernel.program)
        return self._sharded

    def _device_kernel_failed(self, kern) -> None:
        """Runtime fault in a device kernel: pin this engine off that path
        (throughput cost, never liveness)."""
        if kern is self._pallas_kernel:
            self._use_pallas = False
        if self._sharded not in (None, False) and kern is self._sharded:
            self._sharded = False
        if isinstance(kern, _LanePlacedKernel):
            # a placed kernel's failure is usually the BASE kernel's
            # (Mosaic bug, not chip health): pin the base path too, or
            # every lane rebuilds a wrapper around the same failing
            # kernel and healthy chips trip their breakers on software
            if kern.base is self._pallas_kernel:
                self._use_pallas = False
            self._lane_kernels.pop(kern.lane.index, None)

    def _device_kernel(self, lane=None):
        """Segment-tier kernel selection.  A lane-bound dispatch (sharded
        processor worker on a multi-chip host) gets a single-device kernel
        PLACED on its home chip — independent per-chip execution streams,
        the loongmesh data plane.  Unbound dispatches shard over the full
        mesh when multiple devices are attached, else fused Pallas on TPU
        (one VMEM pass per row block), XLA fusion elsewhere.  Resolved
        once per engine (per lane); the paths are differentially fuzzed
        against each other."""
        if getattr(self, "_kernel_override", None) is not None:
            return self._kernel_override
        if lane is not None:
            k = self._lane_kernels.get(lane.index)
            if k is None:
                k = _LanePlacedKernel(self._single_device_kernel(), lane)
                self._lane_kernels[lane.index] = k
            return k
        sharded = self._maybe_sharded()
        if sharded is not None:
            return sharded
        return self._single_device_kernel()

    def _single_device_kernel(self):
        """Pallas-vs-XLA choice for one device (shared by the default
        path and every lane-placed wrapper)."""
        if self._use_pallas is None:
            self._use_pallas = pallas_by_default()
        if self._use_pallas:
            if self._pallas_kernel is None:
                from ..kernels.field_extract_pallas import PallasExtractKernel
                self._pallas_kernel = PallasExtractKernel(
                    self._segment_kernel.program)
            return self._pallas_kernel
        return self._segment_kernel

    def _match_kernel(self, lane=None):
        """The SEGMENT-tier program as a full-match gate with one result
        word a row (``MatchKernel``, a jit family of its own), placed on
        the lane's chip for a lane-bound dispatch.  An unbound dispatch
        runs it on the default device: a gate over physical lines has no
        use for the mesh."""
        if getattr(self, "_kernel_override", None) is not None:
            return self._kernel_override
        key = lane.index if lane is not None else None
        k = self._match_kernels.get(key)
        if k is None:
            k = MatchKernel(self._segment_kernel.program)
            if lane is not None:
                k = _LanePlacedKernel(k, lane)
            self._match_kernels[key] = k
        return k

    def _host_walker(self):
        """The native C++ scalar walker for this program (degraded tier);
        None when the library is absent or the program exceeds its limits."""
        if not self._native_tried:
            self._native_tried = True
            if self._segment_kernel is not None:
                from .native_exec import try_build
                self._native_exec = try_build(self._segment_kernel.program)
        return self._native_exec

    def warm_host(self) -> None:
        """AOT-build the host execution artifacts (loongfuse variant
        linearization, native walker, DFA byte-table scanner) at pipeline
        start — get_engine calls this so the first data batch never stalls
        on variant compilation.  Direct constructions (tests, ad-hoc) stay
        cheap and build lazily."""
        if self.tier is PatternTier.SEGMENT:
            self._fused_exec()
            self._host_walker()
        elif self.tier is PatternTier.DFA:
            self._dfa_host_scanner()

    @staticmethod
    def _ops_have_trials(ops) -> bool:
        return any(isinstance(op, (Alt, Optional_)) for op in ops)

    def _fused_exec(self):
        """loongfuse host execution (AOT variant linearization + fused
        classify), built lazily on first host parse.  Only trial-heavy
        straight programs profit — a linear program IS the fast path
        already, and pivot programs scan bidirectionally."""
        if not self._fused_tried:
            self._fused_tried = True
            prog = self._segment_kernel.program \
                if self._segment_kernel is not None else None
            if prog is not None and prog.pivot is None \
                    and prog.pivot2 is None \
                    and self._ops_have_trials(prog.ops):
                from .fuse import try_build_single
                self._fused_single = try_build_single(self.pattern)
        return self._fused_single

    def _dfa_host_scanner(self):
        """Fused byte-table scanner over the Tier-2 DFA: the host
        match-gate (multiline classification) at table-walk speed instead
        of a per-row Python `re` loop."""
        if self._dfa_scanner is None and self._dfa_kernel is not None:
            from .fuse import ByteTableScanner
            self._dfa_scanner = ByteTableScanner.from_dfa(
                self._dfa_kernel.dfa)
        return self._dfa_scanner

    def parse_batch(self, arena: np.ndarray, offsets: np.ndarray,
                    lengths: np.ndarray) -> BatchParseResult:
        """Full-match + captures for N events over a shared arena."""
        return self.parse_batch_async(arena, offsets, lengths).result()

    def match_batch_async(self, arena: np.ndarray, offsets: np.ndarray,
                          lengths: np.ndarray,
                          depth: Optional[int] = None) -> "PendingParse":
        """``match_batch`` for a SEGMENT-tier pattern without the wait:
        routed, chunked and windowed exactly as ``parse_batch_async``, on
        the match gate (``_match_kernel``) instead of the extract kernel.
        ``result().ok`` is the booleans; the capture columns are unset."""
        return self.parse_batch_async(arena, offsets, lengths, depth,
                                      pending_cls=PendingMatch)

    def parse_batch_async(self, arena: np.ndarray, offsets: np.ndarray,
                          lengths: np.ndarray,
                          depth: Optional[int] = None,
                          pending_cls=None) -> "PendingParse":
        """Dispatch the parse; `result()` on the returned handle materialises.

        The async device data plane (SURVEY §7 step 4): each device chunk is
        dispatched through DevicePlane under the in-flight byte budget, and
        the host packs chunk N+1 while the device executes chunk N.  Callers
        that hold the PendingParse (runner overlap mode) get cross-group
        overlap too: the device computes group N while the host runs group
        N-1's downstream processors and group N+1's pack.  Host-walker and
        CPU-tier routing are unchanged — those paths return an
        already-materialised PendingParse.

        loongstream: chunks ride batch-ring slots and at most ``depth``
        (default ``LOONG_STREAM_DEPTH``) stay in flight — the ring advance
        (span return of chunk N-depth+1) overlaps packing/H2D of N+1 and
        device compute of N.  ``depth=1`` forces the synchronous
        submit→materialise round trip."""
        offsets = np.asarray(offsets, dtype=np.int64)
        lengths = np.asarray(lengths, dtype=np.int32)
        n = len(offsets)
        C = max(self.num_caps, 1)
        if n and self.tier is PatternTier.SEGMENT:
            if routes_to_host(lengths,
                              lambda: self._host_walker() is not None):
                fx = self._fused_exec()
                if fx is not None:
                    k_ok, k_off, k_len = fx.parse(arena, offsets, lengths)
                    _note_rows("host_walker", n)
                    return PendingParse.ready(
                        BatchParseResult(k_ok, k_off, k_len))
                nat = self._host_walker()
                if nat is not None:
                    k_ok, k_off, k_len = nat(arena, offsets, lengths)
                    _note_rows("host_walker", n)
                    return PendingParse.ready(
                        BatchParseResult(k_ok, k_off, k_len))
        ok = np.zeros(n, dtype=bool)
        cap_off = np.zeros((n, C), dtype=np.int32)
        cap_len = np.full((n, C), -1, dtype=np.int32)
        if n == 0:
            return PendingParse.ready(BatchParseResult(ok, cap_off, cap_len))

        max_bucket = LENGTH_BUCKETS[-1]
        over = lengths > max_bucket
        device_idx = np.nonzero(~over)[0]
        cpu_idx = np.nonzero(over)[0]

        if self.tier is PatternTier.CPU or self._segment_kernel is None:
            cpu_idx = np.arange(n)
            device_idx = np.array([], dtype=np.int64)

        pending = (pending_cls or PendingParse)(
            self, arena, offsets, lengths, ok, cap_off, cap_len, cpu_idx,
            depth=depth)
        if len(device_idx):
            pending.dispatch(device_idx)
        return pending

    def _cpu_fallback_rows(self, arena, offsets, lengths, cpu_idx,
                           ok, cap_off, cap_len) -> None:
        for i in cpu_idx:
            o, ln = int(offsets[i]), int(lengths[i])
            m = self._re.fullmatch(bytes(arena[o : o + ln].tobytes()))
            if m is not None:
                ok[i] = True
                for g in range(self.num_caps):
                    s, e = m.span(g + 1)
                    if s >= 0:
                        cap_off[i, g] = o + s
                        cap_len[i, g] = e - s

    def _host_parse_rows(self, arena, offsets, lengths, idx,
                         ok, cap_off, cap_len) -> None:
        """Host-tier parse of selected rows, spans arena-absolute — the
        chip-lane RESPILL path (loongmesh): a tripped lane's shard parses
        here, synchronously, so a single-chip fault costs throughput on
        that lane only — never events, never the rest of the mesh.  Tier
        order mirrors the degraded-mode routing: fused exec → native
        walker → CPU `re`."""
        if len(idx) == 0:
            return
        fx = self._fused_exec()
        nat = fx if fx is not None else self._host_walker()
        if nat is not None:
            run = nat.parse if fx is not None else nat
            k_ok, k_off, k_len = run(arena, offsets[idx], lengths[idx])
            ok[idx] = k_ok
            cap_off[idx] = k_off
            cap_len[idx] = k_len
            return
        self._cpu_fallback_rows(arena, offsets, lengths, idx,
                                ok, cap_off, cap_len)

    def match_batch(self, arena: np.ndarray, offsets: np.ndarray,
                    lengths: np.ndarray) -> np.ndarray:
        """Full-match boolean only (filtering) — can use the DFA tier."""
        offsets = np.asarray(offsets, dtype=np.int64)
        lengths = np.asarray(lengths, dtype=np.int32)
        n = len(offsets)
        if n == 0:
            return np.zeros(0, dtype=bool)
        if self.tier is PatternTier.SEGMENT:
            return self.parse_batch(arena, offsets, lengths).ok
        if self.tier is PatternTier.DFA:
            # host route (loongfuse): the fused byte-table scanner walks
            # the SAME automaton the device kernel runs, at native table
            # speed — degraded mode, and small batches where the fixed
            # dispatch round trip dwarfs any host scan; explicit
            # device-kernel forces win, as in parse_batch
            if _pallas_enabled() is None \
                    and os.environ.get("LOONG_NATIVE_T1") != "0" \
                    and (_native_host_mode()
                         or int(lengths.sum()) < _device_min_bytes() // 6):
                sc = self._dfa_host_scanner()
                if sc is not None:
                    tags = sc.scan(arena, offsets, lengths)
                    return (tags & 1).astype(bool)
            ok = np.zeros(n, dtype=bool)
            max_bucket = LENGTH_BUCKETS[-1]
            over = lengths > max_bucket
            device_idx = np.nonzero(~over)[0]
            for chunk in _chunks(device_idx, MAX_BATCH):
                d_off = offsets[chunk]
                d_len = lengths[chunk]
                L = pick_length_bucket(int(d_len.max())) or max_bucket
                batch = pack_rows(arena, d_off, d_len, L)
                # synchronous chunked match tier (DFA-tier match_batch):
                # a standalone boolean gate, not a fusable stage run
                # loonglint: disable=host-bounce
                k_ok = np.asarray(self._dfa_kernel(batch.rows, batch.lengths))
                ok[chunk] = k_ok[: batch.n_real]
            for i in np.nonzero(over)[0]:
                o, ln = int(offsets[i]), int(lengths[i])
                ok[i] = self._re.fullmatch(bytes(arena[o : o + ln].tobytes())) is not None
            return ok
        # CPU tier
        ok = np.zeros(n, dtype=bool)
        for i in range(n):
            o, ln = int(offsets[i]), int(lengths[i])
            ok[i] = self._re.fullmatch(bytes(arena[o : o + ln].tobytes())) is not None
        return ok


class PendingParse:
    """A parse whose device chunks are in flight.

    The chunks ride one `DeviceStream` window (ops/device_stream.py), which
    owns the ring discipline: at most ``depth`` chunks in flight, the
    oldest materialised before the next is packed, this parse's own oldest
    drained when the byte budget or the lane's share would block, and slot,
    budget and lane bytes given back on every path.  What is this class's
    own: which kernel a chunk is submitted on, what becomes of a chunk
    whose materialisation failed, and where the spans are written.
    `result()` runs the CPU-tier fallback rows (host work, overlapping the
    device), then materialises remaining chunks in order.

    Error semantics: an injected chaos fault (``device_plane.h2d`` /
    ``device_plane.ring_advance`` / ``device_plane.submit``) costs that one
    chunk a synchronous re-run — never the parse, never the ring order.  A
    Pallas/Mosaic failure at materialisation pins the engine to the XLA
    path and re-runs that chunk synchronously; failures on the XLA kernel
    itself propagate.

    loongmesh: a lane-bound worker's chunks dispatch on its home chip
    (``device_plane.chip_lane.<i>`` chaos point, per-chip budget share,
    per-chip tuner floors).  An injected single-chip fault feeds the
    lane's breaker and respills that chunk to host parsing; a tripped
    (OPEN) lane respills its whole shard pre-dispatch until the half-open
    probe re-closes it — the other chips' lanes keep running throughout.
    """

    __slots__ = ("engine", "arena", "offsets", "lengths", "ok", "cap_off",
                 "cap_len", "cpu_idx", "_window", "_result", "depth")

    #: the window's timeline tag (xprof dispatch decomposition)
    program = "regex"

    def __init__(self, engine, arena, offsets, lengths, ok, cap_off, cap_len,
                 cpu_idx, depth=None):
        self.engine = engine
        self.arena = arena
        self.offsets = offsets
        self.lengths = lengths
        self.ok = ok
        self.cap_off = cap_off
        self.cap_len = cap_len
        self.cpu_idx = cpu_idx
        self._window = None             # opened by dispatch()
        self._result = None
        self.depth = depth

    @classmethod
    def ready(cls, result: BatchParseResult) -> "PendingParse":
        """An already-materialised parse (host tiers, empty input): no
        device chunk, no window."""
        p = cls(None, None, None, None, result.ok, result.cap_off,
                result.cap_len, ())
        p._result = result
        return p

    @property
    def done(self) -> bool:
        return self._result is not None

    def dispatch(self, device_idx: np.ndarray) -> None:
        from ..device_plane import DevicePlane
        # loongmesh: a lane-bound worker thread dispatches on its home
        # chip (source → worker → chip affinity); unbound dispatch shards
        # over the full mesh (or runs single-device)
        lane = chip_lanes.current_lane()
        window = self._window = DevicePlane.instance().open_stream(
            self.depth, program=self.program, lane=lane,
            recover=self._recover, deliver=self._deliver)
        # the extract path's first choice is what routing reports, whoever
        # dispatches first (the match gate has one kernel, nothing to choose)
        _note_first_choice(self._first_choice(lane))
        try:
            for chunk in _chunks(device_idx, MAX_BATCH):
                if not window.admit(_n_rows(chunk)):
                    # this chip is sick: its shard parses on the host, in
                    # order, synchronously (ledger-conserved)
                    self._host_rows(chunk)
                    continue
                # re-read the kernel PER CHUNK: the ring advance in admit
                # (or the budget-wait hook inside submit) may have pinned
                # the engine to the XLA path mid-dispatch — each chunk
                # must record the kernel it was actually SUBMITTED on, or
                # the materialise-time fallback check misfires.
                # Buffer donation: a kernel offering a donating variant
                # (the sharded kernel) gets it on this path — each
                # dispatch's inputs are transient staging copies.  A bare
                # single-device kernel rides as itself, and the window
                # takes its packed entry (ops/packed_io.py).
                kern = self._kernel_for(lane)
                call = getattr(kern, "donated_call", None) or kern
                if lane is not None:
                    # chip-lane chaos: dispatch passes this lane's fault
                    # point; the chunk keeps the bare kernel so recovery
                    # re-runs never re-fire the injection
                    call = lane_gated(lane, call)
                window.submit_rows(call, self.arena, self.offsets[chunk],
                                   self.lengths[chunk], tag=chunk,
                                   kernel=kern)
        except BaseException:
            # a failed pack/submit must not strand what the chunks already
            # submitted hold (round-5 leak): the caller abandons this
            # parse, nobody will result() them
            window.abandon()
            raise

    def _kernel_for(self, lane):
        """The kernel a chunk is submitted on (read per chunk)."""
        return self.engine._device_kernel(lane)

    def _first_choice(self, lane):
        """The kernel ``routing.kernel_first_choice`` names."""
        return self.engine._device_kernel(lane)

    def _host_rows(self, chunk) -> None:
        """The rows of a chunk that cannot ride the device, settled on the
        host tiers into the same result rows."""
        self.engine._host_parse_rows(
            self.arena, self.offsets, self.lengths, chunk,
            self.ok, self.cap_off, self.cap_len)

    def _recover(self, c, exc):
        """A chunk whose materialisation raised (the window's callback):
        its spans from another path, or raise."""
        engine = self.engine
        if isinstance(exc, ChipLaneFault):
            # injected SINGLE-CHIP fault (device_plane.chip_lane.<i>): the
            # window feeds the lane breaker — enough of these trip it OPEN
            # and later chunks respill pre-dispatch; THIS chunk's shard
            # parses on the host.  Events conserved, order kept (results
            # land in the same rows), the other chips' lanes never notice.
            self._host_rows(c.tag)
            return None
        kern = c.kernel
        if not isinstance(exc, chaos.ChaosFault):
            if kern is engine._segment_kernel or \
                    getattr(engine, "_kernel_override", None) is not None:
                raise exc
            # Mosaic/mesh/chip runtime failure must cost throughput,
            # never liveness: pin this engine off the failed path and
            # re-run the chunk on the proven XLA kernel.  A lane kernel's
            # REAL failure also counts against its chip's breaker (the
            # window's report) — repeated ones trip the lane to host
            # respill.
            _note_kernel_fallback(
                "device kernel failed for %r; falling back to XLA path",
                engine.pattern)
            engine._device_kernel_failed(kern)
            # lane dispatches keep their placement (the pin rebuilds a
            # wrapper around the proven XLA kernel); unplaced dispatches
            # fall to XLA directly
            kern = engine._segment_kernel if self._window.lane is None \
                else engine._device_kernel(self._window.lane)
        # else an injected async-stage fault (h2d / ring_advance /
        # submit): it must error only THIS chunk — the slot still holds
        # the packed rows, so re-run on the same kernel and keep the ring
        # moving in order
        return _rerun(kern, c)

    def _deliver(self, c, outs) -> None:
        k_ok, k_off, k_len = outs
        chunk, batch = c.tag, c.batch
        n = batch.n_real
        self.ok[chunk] = k_ok[:n]
        # row-relative -> arena-absolute
        self.cap_off[chunk] = k_off[:n] + batch.origins[:n, None]
        self.cap_len[chunk] = k_len[:n]

    def result(self) -> BatchParseResult:
        if self._result is not None:
            return self._result
        # CPU-tier rows first: host work that overlaps in-flight device chunks
        if len(self.cpu_idx):
            _note_rows("cpu_re", len(self.cpu_idx))
            self.engine._cpu_fallback_rows(
                self.arena, self.offsets, self.lengths, self.cpu_idx,
                self.ok, self.cap_off, self.cap_len)
        if self._window is not None:
            self._window.drain()
        self._result = BatchParseResult(self.ok, self.cap_off, self.cap_len)
        # drop references so the arena/batches free promptly; the window
        # holds this object's bound methods, so letting go of it also
        # undoes the cycle (no wait for the collector)
        self.arena = self.offsets = self.lengths = self._window = None
        return self._result

    def abandon(self) -> None:
        """The caller gives this parse up with chunks still in flight (its
        own failure after the dispatch: nobody will ``result()`` them):
        slots, budget and lane bytes return unmaterialised."""
        window, self._window = self._window, None
        if window is not None:
            window.abandon()


class PendingMatch(PendingParse):
    """A full-match gate whose device chunks are in flight: the
    ``PendingParse`` window, routing and host tiers, with the match gate
    (``RegexEngine._match_kernel``) as the kernel and one result word a
    row to deliver.  The gate is plain XLA, so there is no other device
    path to fall back to: an injected async-stage fault re-runs the chunk
    on the same kernel, a chip-lane fault parses the shard on the host,
    anything else propagates."""

    __slots__ = ("calls",)

    program = "line_classify"

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        #: (rows, width) of every chunk delivered: the shapes of the calls
        self.calls = []

    def _kernel_for(self, lane):
        return self.engine._match_kernel(lane)

    def _recover(self, c, exc):
        if isinstance(exc, ChipLaneFault):
            self._host_rows(c.tag)
            return None
        if not isinstance(exc, chaos.ChaosFault):
            raise exc
        return _rerun(c.kernel, c)

    def _deliver(self, c, outs) -> None:
        self.ok[c.tag] = outs[0][:c.batch.n_real] != 0
        self.calls.append(c.batch.rows.shape)


class PendingMatchList(PendingParse):
    """An ordered ``Match`` list whose device chunks are in flight: every
    member's extract and the first-match choice are ONE program
    (``ops/kernels/match_list.py`` ``MatchListKernel``), so a chunk
    delivers each row's member (-1: none took it) and its spans in the
    columns of the union of the members' keys.  The ``PendingParse``
    window, chunking and lane placement; ``result().ok`` is the int32
    member index, ``cap_off`` / ``cap_len`` are ``[N, K]``.

    No engine stands behind it and it has no host tier of its own: the
    caller applies the routing rule (``routes_to_host``) to the whole
    group before it builds one, and keeps rows over the largest length
    bucket to itself.  The rows of a chunk that cannot ride — a sick chip
    lane, a chip-lane fault, a real failure of the program — are named in
    ``host_rows`` for the caller's own host path; a real failure is also
    counted (``device.routing.kernel_fallbacks_total``) and sets
    ``failed``, on which the caller gives the program up.  An injected
    async-stage fault re-runs the chunk on the same program.

    Every small numpy call costs the worker a hand-over of the
    interpreter lock in the agent (0.05-0.1 ms each beside the reader and
    the sender: PERF.md section 6, PR 35, call 4), so the common case —
    every row of the group rides, in one chunk — makes none it can avoid:
    ``dispatch()`` without an index array chunks by slices, and the one
    chunk's copy back IS the result (no buffers to fill, no scatter; the
    member and length columns stay read-only views of it)."""

    __slots__ = ("kernel", "host_rows", "failed", "rode")

    program = "grok_match_list"

    def __init__(self, kernel, arena, offsets, lengths, depth=None):
        super().__init__(None, arena, offsets, lengths, None, None, None,
                         (), depth=depth)
        self.kernel = kernel
        #: the chunks (index arrays or slices) left to the caller's host
        #: path
        self.host_rows = []
        self.failed = False
        #: rows handed to the window
        self.rode = 0

    def dispatch(self, device_idx: Optional[np.ndarray] = None) -> None:
        """``device_idx`` None: every row rides."""
        n = len(self.offsets)
        self.rode = n if device_idx is None else len(device_idx)
        super().dispatch(n if device_idx is None else device_idx)

    def _kernel_for(self, lane):
        if lane is None:
            return self.kernel
        return _LanePlacedKernel(self.kernel, lane)

    def _first_choice(self, lane):
        return self.kernel

    def _host_rows(self, chunk) -> None:
        self.host_rows.append(chunk)

    def _recover(self, c, exc):
        if isinstance(exc, ChipLaneFault):
            self._host_rows(c.tag)
            return None
        if not isinstance(exc, chaos.ChaosFault):
            # as the extract's Mosaic failure: throughput, never liveness
            _note_kernel_fallback("the Match list program failed; its rows "
                                  "take the per-member path")
            self.failed = True
            self._host_rows(c.tag)
            return None
        return _rerun(c.kernel, c)

    def _buffers(self) -> None:
        n, K = len(self.offsets), self.kernel.num_keys
        self.ok = np.full(n, -1, dtype=np.int32)
        self.cap_off = np.zeros((n, K), dtype=np.int32)
        self.cap_len = np.full((n, K), -1, dtype=np.int32)

    def _deliver(self, c, outs) -> None:
        member, k_off, k_len = outs
        chunk, batch = c.tag, c.batch
        n = batch.n_real
        if self.ok is None:
            if isinstance(chunk, slice) and n == len(self.offsets):
                # the whole group in one chunk
                self.ok, self.cap_len = member[:n], k_len[:n]
                self.cap_off = k_off[:n] + batch.origins[:n, None]
                return
            self._buffers()
        self.ok[chunk] = member[:n]
        # row-relative -> arena-absolute
        self.cap_off[chunk] = k_off[:n] + batch.origins[:n, None]
        self.cap_len[chunk] = k_len[:n]

    def result(self) -> BatchParseResult:
        if self._result is None:
            if self._window is not None:
                self._window.drain()
            if self.ok is None:
                self._buffers()         # no chunk delivered anything
        return super().result()

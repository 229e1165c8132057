"""Unified regex engine: tier dispatch + batch orchestration.

The single entry point processors use.  Given a pattern, picks the execution
tier (segment kernel / DFA kernel / CPU `re`), owns geometry bucketing and
row packing, and returns arena-absolute capture spans so downstream stays
zero-copy (SURVEY.md §7 step 4: spans must index the ORIGINAL arena).

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
from .. import chip_lanes, xprof
from ..chip_lanes import ChipLaneFault, lane_gated
from ..device_batch import (LENGTH_BUCKETS, MAX_BATCH, pack_rows, pad_batch,
                            pick_length_bucket)
from ..device_stream import (FP_RING_ADVANCE, auto_tuner, batch_ring,
                             h2d_gated, stream_depth)
from ..kernels.dfa_scan import DFAMatchKernel
from ..kernels.field_extract import ExtractKernel
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
# cumulative for the life of the process (chip_smoke.py reads them through
# /debug/status).  Rows that did go are the batch ring's ``real_rows``
# (``streaming.ring``); lane respills are the lanes' own
# ``respilled_events`` (``mesh.lanes``).
_route_lock = threading.Lock()
_route_rows = {"host_walker": 0, "cpu_re": 0}
_kernel_first_choice: Optional[str] = None
_kernel_fallbacks = 0


def _note_rows(tier: str, n: int) -> None:
    with _route_lock:
        _route_rows[tier] += n


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
    fixed dispatch latency from effective host<->device bandwidth."""
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
        for _ in range(3):
            t0 = time.perf_counter()
            np.asarray(g(rows))
            samples.append(time.perf_counter() - t0)
        times.append(sorted(samples)[1])
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


def _chunks(idx: np.ndarray, size: int):
    for i in range(0, len(idx), size):
        yield idx[i : i + size]


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
    routing and backend forces are resolved once per engine — tests and
    the bench chips sweep clear the cache after changing them so the next
    ``get_engine`` re-resolves against the new environment."""
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
    collectives on the batch path.  Exposes the same ``donated_call``
    protocol as the base kernels (the placed copies are transient staging
    buffers, safe to donate)."""

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

    def donated_call(self, rows, lengths):
        rows_d, lens_d = self._place(rows, lengths)
        don = getattr(self.base, "donated_call", None)
        return don(rows_d, lens_d) if don is not None \
            else self.base(rows_d, lens_d)


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
            forced = _pallas_enabled()
            if forced is not None:
                self._use_pallas = forced
            else:
                import jax
                self._use_pallas = jax.default_backend() == "tpu"
        if self._use_pallas:
            if self._pallas_kernel is None:
                from ..kernels.field_extract_pallas import PallasExtractKernel
                self._pallas_kernel = PallasExtractKernel(
                    self._segment_kernel.program)
            return self._pallas_kernel
        return self._segment_kernel

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

    def parse_batch_async(self, arena: np.ndarray, offsets: np.ndarray,
                          lengths: np.ndarray,
                          depth: Optional[int] = None) -> "PendingParse":
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
        submit→materialise round trip (the bench sweep baseline)."""
        offsets = np.asarray(offsets, dtype=np.int64)
        lengths = np.asarray(lengths, dtype=np.int32)
        n = len(offsets)
        C = max(self.num_caps, 1)
        if n and self.tier is PatternTier.SEGMENT:
            use_host = _native_host_mode()
            if not use_host and _pallas_enabled() is None \
                    and os.environ.get("LOONG_NATIVE_T1") != "0":
                # accelerator backend: small batches still lose to the fixed
                # dispatch round trip — route them to the native walker
                # (explicit LOONG_PALLAS / LOONG_NATIVE_T1 forces win)
                nat = self._host_walker()
                use_host = (nat is not None
                            and int(lengths.sum()) < _device_min_bytes())
            if use_host:
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

        pending = PendingParse(self, arena, offsets, lengths,
                               ok, cap_off, cap_len, cpu_idx, depth=depth)
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

    loongstream dispatch discipline: `dispatch()` packs each device chunk
    into a leased batch-ring slot (pre-allocated fixed-geometry buffers —
    no per-dispatch allocation on the H2D path) and submits it through the
    DevicePlane, keeping at most ``depth`` chunks in flight: a full window
    first advances the ring (materialises the OLDEST chunk), so the host
    packs chunk N+1 while the device executes N and N-depth+1 returns
    spans.  When the in-flight byte budget would block a submit, the
    oldest owned future is drained first (never sleep in submit while
    owning the budget you wait for — see DevicePlane.would_block).
    `result()` runs the CPU-tier fallback rows (host work, overlapping the
    device), then materialises remaining chunks in order.

    Error semantics: an injected chaos fault (``device_plane.h2d`` /
    ``device_plane.ring_advance`` / ``device_plane.submit``) costs that one
    chunk a synchronous re-run — never the parse, never the ring order.  A
    Pallas/Mosaic failure at materialisation pins the engine to the XLA
    path and re-runs that chunk synchronously; failures on the XLA kernel
    itself propagate.  Every path releases the chunk's slot and budget.

    loongmesh: a lane-bound worker's chunks dispatch on its home chip
    (``device_plane.chip_lane.<i>`` chaos point, per-chip budget share,
    per-chip tuner floors).  An injected single-chip fault feeds the
    lane's breaker and respills that chunk to host parsing; a tripped
    (OPEN) lane respills its whole shard pre-dispatch until the half-open
    probe re-closes it — the other chips' lanes keep running throughout.
    """

    __slots__ = ("engine", "arena", "offsets", "lengths", "ok", "cap_off",
                 "cap_len", "cpu_idx", "_chunks_pending", "_result", "kern",
                 "depth")

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
        # [(chunk_idx, DeviceBatch, BatchSlot, DeviceFuture, kernel)]
        self._chunks_pending = []
        self._result = None
        self.kern = None
        self.depth = max(1, depth if depth is not None else stream_depth())

    @classmethod
    def ready(cls, result: BatchParseResult) -> "PendingParse":
        p = cls.__new__(cls)
        p._result = result
        p._chunks_pending = []
        p.cpu_idx = ()
        return p

    @property
    def done(self) -> bool:
        return self._result is not None

    def dispatch(self, device_idx: np.ndarray) -> None:
        from ..device_plane import DevicePlane
        plane = DevicePlane.instance()
        ring = batch_ring()
        tuner = auto_tuner()
        # loongmesh: a lane-bound worker thread dispatches on its home
        # chip (source → worker → chip affinity); unbound dispatch shards
        # over the full mesh (or runs single-device)
        lane = chip_lanes.current_lane()
        lane_count = chip_lanes.router().lane_count() if lane is not None \
            else 0
        self.kern = self.engine._device_kernel(lane)
        _note_first_choice(self.kern)
        max_bucket = LENGTH_BUCKETS[-1]
        try:
            for chunk in _chunks(device_idx, MAX_BATCH):
                if lane is not None and not lane.breaker.allow_probe():
                    # lane breaker OPEN (or the half-open probe slot is
                    # already in flight): this chip is sick — respill its
                    # shard to host parsing.  Events still parse, in
                    # order, synchronously (ledger-conserved); the other
                    # chips' lanes keep running untouched.
                    lane.note_respill(len(chunk))
                    self.engine._host_parse_rows(
                        self.arena, self.offsets, self.lengths, chunk,
                        self.ok, self.cap_off, self.cap_len)
                    continue
                # ring advance: a full window materialises its oldest chunk
                # (span return of N-depth+1) before packing N+1
                while len(self._chunks_pending) >= self.depth:
                    self._drain_one()
                # per-chip budget share: a lane holding more than its
                # slice of the plane budget drains its own oldest chunk
                # first — one slow chip backs up its own lane, not the
                # whole plane (same never-sleep-owning-budget rule)
                while lane is not None \
                        and lane.over_share(plane, lane_count) \
                        and self._chunks_pending:
                    self._drain_one()
                # re-read the kernel PER CHUNK: the drain above (or the
                # budget-wait hook inside submit) may have pinned the
                # engine to the XLA path mid-dispatch — each pending tuple
                # must record the kernel its chunk was actually SUBMITTED
                # on, or the materialise-time fallback check misfires.
                # Buffer donation: a kernel offering a donating variant
                # gets it on this path — each dispatch's inputs are
                # transient staging copies, so XLA may reuse their HBM for
                # the outputs instead of allocating per dispatch.
                sub_kern = self.kern
                call = getattr(sub_kern, "donated_call", None) or sub_kern
                if lane is not None:
                    # chip-lane chaos: dispatch passes this lane's fault
                    # point; the bare kernel stays in the pending tuple so
                    # recovery re-runs never re-fire the injection
                    call = lane_gated(lane, call)
                d_off = self.offsets[chunk]
                d_len = self.lengths[chunk]
                L = pick_length_bucket(int(d_len.max()) if len(d_len) else 1) \
                    or max_bucket
                lane_idx = lane.index if lane is not None else None
                B = pad_batch(len(chunk),
                              min_batch=tuner.min_batch_for(L, lane_idx),
                              multiple_of=getattr(sub_kern,
                                                  "batch_multiple", 1))
                slot = ring.lease(B, L)
                try:
                    batch = slot.pack(self.arena, d_off, d_len,
                                      lane=lane_idx)
                    fut = plane.submit(h2d_gated(call),
                                       (batch.rows, batch.lengths),
                                       batch.rows.nbytes,
                                       on_wait=self._drain_if_pending)
                except BaseException:
                    slot.release()
                    raise
                xprof.note_dispatch(fut, "regex", f"{B}x{L}",
                                    slot.pack_t0, slot.pack_dur)
                if lane is not None:
                    lane.note_pack(B, batch.n_real)
                    lane.note_dispatch(batch.rows.nbytes)
                self._chunks_pending.append((chunk, batch, slot, fut,
                                             sub_kern, lane))
        except BaseException:
            # a failed pack/submit must not strand the budget (or the ring
            # slots, or the lanes' in-flight accounting) the
            # already-submitted futures hold (round-5 leak): force-release
            # them — the caller abandons this parse, nobody will result()
            # them
            for _, b, slot, fut, _k, ln in self._chunks_pending:
                fut.release()
                if ln is not None:
                    ln.note_done(b.rows.nbytes)
                    # an abandoned chunk may hold the lane's half-open
                    # probe slot — release it (no health sample) so the
                    # lane is not forced to respill until probe_timeout_s
                    ln.breaker.on_inconclusive()
                slot.release()
            self._chunks_pending.clear()
            raise

    def _drain_if_pending(self) -> bool:
        """Budget-wait hook: materialise our oldest in-flight chunk so the
        bytes we hold are released while we wait (DevicePlane._acquire's
        deadlock-freedom rule)."""
        if not self._chunks_pending:
            return False
        self._drain_one()
        return True

    def _drain_one(self) -> None:
        chunk, batch, slot, fut, sub_kern, lane = self._chunks_pending.pop(0)
        try:
            try:
                chaos.faultpoint(FP_RING_ADVANCE)
                k_ok, k_off, k_len = fut.result()
                if lane is not None:
                    # healthy materialisation on this chip: breaker sample
                    # (re-closes a half-open lane when this was the probe)
                    lane.breaker.on_success()
            except ChipLaneFault:
                # injected SINGLE-CHIP fault (device_plane.chip_lane.<i>):
                # feed the lane breaker — enough of these trip it OPEN and
                # later chunks respill pre-dispatch — and respill THIS
                # chunk's shard to host parsing.  Events conserved, order
                # kept (results land in the same slots), the other chips'
                # lanes never notice.
                fut.release()
                lane.breaker.on_failure()
                lane.note_fault()
                lane.note_respill(int(batch.n_real))
                self.engine._host_parse_rows(
                    self.arena, self.offsets, self.lengths, chunk,
                    self.ok, self.cap_off, self.cap_len)
                return
            except chaos.ChaosFault:
                # injected async-stage fault (h2d / ring_advance / submit):
                # it must error only THIS chunk — the slot still holds the
                # packed rows, so re-run synchronously and keep the ring
                # moving in order.  fut.release() is a no-op if result()
                # already returned the budget.  The chunk may hold the
                # lane's half-open probe slot: its outcome MUST reach the
                # breaker (success on a clean re-run, inconclusive on a
                # re-run failure) or the slot wedges and the whole lane
                # respills for probe_timeout_s.
                fut.release()
                try:
                    outs = sub_kern(batch.rows, batch.lengths)
                except BaseException:
                    if lane is not None:
                        lane.breaker.on_inconclusive()
                    raise
                if lane is not None:
                    lane.breaker.on_success()
                # chaos-fault recovery re-run: the designed exception path
                # loonglint: disable=host-bounce
                k_ok, k_off, k_len = (np.asarray(a) for a in outs)
            except Exception:  # noqa: BLE001
                if sub_kern is self.engine._segment_kernel or \
                        getattr(self.engine, "_kernel_override",
                                None) is not None:
                    raise
                # Mosaic/mesh/chip runtime failure must cost throughput,
                # never liveness: pin this engine off the failed path and
                # re-run the chunk on the proven XLA kernel.  A lane
                # kernel's REAL failure also counts against its chip's
                # breaker — repeated ones trip the lane to host respill.
                # Production fault handling, never silent: every fallback
                # is counted (``device.routing.kernel_fallbacks_total``)
                # and chip_smoke.py fails on a non-zero count.
                global _kernel_fallbacks
                with _route_lock:
                    _kernel_fallbacks += 1
                from ...utils.logger import get_logger
                get_logger("regex").exception(
                    "device kernel failed for %r; falling back to XLA path",
                    self.engine.pattern)
                if lane is not None:
                    lane.breaker.on_failure()
                    lane.note_fault()
                self.engine._device_kernel_failed(sub_kern)
                # lane dispatches keep their placement (the pop above
                # plus base pinning rebuilds a wrapper around the proven
                # XLA kernel); unplaced dispatches fall to XLA directly
                self.kern = self.engine._segment_kernel if lane is None \
                    else self.engine._device_kernel(lane)
                # kernel-failure fallback re-run on the proven XLA path
                # loonglint: disable=host-bounce
                k_ok, k_off, k_len = (np.asarray(a) for a in
                                      self.kern(batch.rows, batch.lengths))
            k_ok = k_ok[: batch.n_real]
            k_off = k_off[: batch.n_real]
            k_len = k_len[: batch.n_real]
            self.ok[chunk] = k_ok
            # row-relative -> arena-absolute
            self.cap_off[chunk] = k_off + batch.origins[: batch.n_real, None]
            self.cap_len[chunk] = k_len
        finally:
            if lane is not None:
                lane.note_done(batch.rows.nbytes)
            # the slot may be repacked the moment it returns to the ring:
            # release only after the spans were copied out above
            slot.release()

    def result(self) -> BatchParseResult:
        if self._result is not None:
            return self._result
        # CPU-tier rows first: host work that overlaps in-flight device chunks
        if len(self.cpu_idx):
            _note_rows("cpu_re", len(self.cpu_idx))
            self.engine._cpu_fallback_rows(
                self.arena, self.offsets, self.lengths, self.cpu_idx,
                self.ok, self.cap_off, self.cap_len)
        try:
            while self._chunks_pending:
                self._drain_one()
        except BaseException:
            # a failed chunk must not leak the others' in-flight budget —
            # or their ring slots, or their lanes' in-flight accounting
            for _, b, slot, fut, _k, ln in self._chunks_pending:
                try:
                    fut.result()
                except Exception:  # noqa: BLE001 — releasing, not consuming
                    pass
                if ln is not None:
                    ln.note_done(b.rows.nbytes)
                    ln.breaker.on_inconclusive()   # see dispatch cleanup
                slot.release()
            self._chunks_pending.clear()
            raise
        self._result = BatchParseResult(self.ok, self.cap_off, self.cap_len)
        # drop references so the arena/batches free promptly
        self.arena = self.offsets = self.lengths = None
        return self._result

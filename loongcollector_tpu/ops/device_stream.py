"""loongstream: the streaming device pipeline (batch rings + auto-tuner).

A synchronous submit→materialise loop leaves the device idle during batch
assembly, H2D/D2H transfer and the round trip itself (exactly what
loongprof's ``device_idle_while_backlogged_ms`` measures).  This module
closes that gap on the host side of the dispatch:

* **BatchRing / BatchSlot** — a persistent ring of pre-allocated
  fixed-geometry batch buffers per ``(B, L)`` geometry.  Packing reuses the
  slot's arrays instead of allocating per dispatch (no allocator churn, no
  fresh page faults on the H2D path), and every pack records padding waste
  (padded-vs-real rows and bytes) per geometry, observable in
  /debug/status and the Prometheus exposition.  Slots are leased and
  MUST be released exactly once — the loonglint acquire-release checker
  enforces the pairing the same way it does for device-budget futures.

* **DeviceStream** — the pipelined dispatch window (ParPaRaw's feeding
  discipline): up to ``depth`` batches stay in flight; submitting into a
  full window first materialises the OLDEST batch (the ring advance), so
  the host packs/H2Ds batch N+1 while the device computes N and batch
  N-depth+1 returns spans.  Results complete strictly in submit order; a
  fault mid-ring errors only that batch's entry, releases its slot and
  budget, and never stalls or reorders the ring.

* **WidthAutoTuner** — replaces the static ``MIN_BATCH``/``pad_batch``
  policy with runtime-chosen B floors per length bucket (driven by the
  measured padding fraction) and a flush deadline for the worker lane
  rings (driven by the device-utilization accounting: when
  ``device_idle_while_backlogged_ms`` grows, batches ride the ring longer
  to buy overlap; when the device keeps up, the deadline shrinks back for
  latency).

Chaos fault points ``device_plane.h2d`` (pack/transfer stage — the window
submits every call through :func:`h2d_gated`) and
``device_plane.ring_advance`` (materialise stage; an owner may name its
own point) make the async stages stormable.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import chaos
from .. import trace
from . import chip_lanes, xprof
from .device_batch import (LENGTH_BUCKETS, MIN_BATCH, pack_rows, pad_batch,
                           pick_length_bucket)
from .packed_io import packed_rows

FP_RING_ADVANCE = chaos.register_point("device_plane.ring_advance")
FP_H2D = chaos.register_point("device_plane.h2d")

ENV_DEPTH = "LOONG_STREAM_DEPTH"
ENV_TUNER = "LOONG_STREAM_TUNER"

DEFAULT_DEPTH = 3
MAX_DEPTH = 8

#: the tuner never shrinks a geometry floor below this (a 32-row dispatch
#: still amortises its fixed cost ~32x over a single-row call)
MIN_TUNED_FLOOR = 32


def stream_depth(env=os.environ) -> int:
    """Pipeline depth: how many batches one dispatch loop keeps in flight
    (pack N+1 / compute N / span-return N-1 needs 3).  ``LOONG_STREAM_DEPTH``
    overrides; clamped to [1, 8] — 1 degenerates to the synchronous
    submit→materialise round-trip."""
    raw = env.get(ENV_DEPTH)
    if raw:
        try:
            return max(1, min(int(raw), MAX_DEPTH))
        except ValueError:
            pass
    return DEFAULT_DEPTH


def tuner_enabled(env=os.environ) -> bool:
    return env.get(ENV_TUNER) != "0"


def h2d_gated(kernel):
    """Wrap a kernel so the dispatch-side pack/H2D stage is a chaos fault
    point: an injected ERROR raises inside the DevicePlane.submit try —
    exactly a kernel failing at dispatch — so only THAT batch's future
    errors (budget released at its consume point) and the ring keeps
    moving.  A DELAY models a slow transfer.  Disabled plane: one global
    read per dispatch."""
    def _gated(*args):
        chaos.faultpoint(FP_H2D)
        return kernel(*args)
    return _gated


# ---------------------------------------------------------------------------
# padding-waste accounting


_pad_hist = None


def padding_fraction_histogram():
    """Per-pack fraction of the device tensor that is padding (rows beyond
    n_real plus the zero tail of every real row): a distribution living
    near 1.0 means the geometry floor, not the data, sizes the dispatch —
    the signal the width auto-tuner acts on."""
    global _pad_hist
    if _pad_hist is None:
        from ..monitor.metrics import shared_histogram
        _pad_hist = shared_histogram("device_batch_padding_fraction",
                                     labels={"component": "device_stream"})
    return _pad_hist


_geom_records: Dict[Tuple[int, int], object] = {}
_geom_records_lock = threading.Lock()


def _geometry_record(B: int, L: int):
    rec = _geom_records.get((B, L))
    if rec is None:
        with _geom_records_lock:
            rec = _geom_records.get((B, L))
            if rec is None:
                from ..monitor.metrics import MetricsRecord
                rec = MetricsRecord(
                    category="device_plane",
                    labels={"component": "batch_ring",
                            "geometry": f"{B}x{L}"})
                _geom_records[(B, L)] = rec
    return rec


class _GeometryStats:
    __slots__ = ("packs", "real_rows", "padded_rows", "real_bytes",
                 "padded_bytes", "slot_allocs", "slot_reuses")

    def __init__(self) -> None:
        self.packs = 0
        self.real_rows = 0
        self.padded_rows = 0
        self.real_bytes = 0
        self.padded_bytes = 0
        self.slot_allocs = 0
        self.slot_reuses = 0

    def as_dict(self) -> dict:
        total = self.real_bytes + self.padded_bytes
        return {
            "packs": self.packs,
            "real_rows": self.real_rows,
            "padded_rows": self.padded_rows,
            "real_bytes": self.real_bytes,
            "padded_bytes": self.padded_bytes,
            "padding_fraction": (self.padded_bytes / total) if total else 0.0,
            "slot_allocs": self.slot_allocs,
            "slot_reuses": self.slot_reuses,
        }


# ---------------------------------------------------------------------------
# batch ring


class BatchSlot:
    """One pre-allocated fixed-geometry batch buffer, leased from the ring.

    What crosses to the device is ONE contiguous array, ``packed``:
    ``B + ceil(4·B / L)`` rows of ``L`` bytes, the first ``B`` the rows
    and the tail the ``B`` lengths as little-endian int32
    (ops/packed_io.py).  ``rows`` and ``lengths`` are numpy views over
    it, so a pack, a recovery re-run on ``(rows, lengths)`` and the byte
    accounting see two arrays; ``origins`` never crosses and stays apart.

    ``pack()`` fills the slot's arrays from the arena (zero-copy reuse of
    the same host pages every generation) and returns the DeviceBatch view;
    ``release()`` returns the slot to its pool — exactly once, after the
    dispatch that used it has materialised (the kernel may alias the
    buffers until then)."""

    __slots__ = ("_ring", "B", "L", "packed", "rows", "lengths", "origins",
                 "_leased", "pack_t0", "pack_dur", "pack_cpu")

    def __init__(self, ring: "BatchRing", B: int, L: int):
        self._ring = ring
        self.B = B
        self.L = L
        self.packed = np.zeros((packed_rows(B, L), L), dtype=np.uint8)
        self.rows = self.packed[:B]
        self.lengths = self.packed[B:].reshape(-1)[:4 * B].view("<i4")
        self.origins = np.zeros(B, dtype=np.int32)
        self._leased = False
        # last pack()'s stopwatch (perf_counter start, dur s, and the
        # packing thread's CPU s) — the dispatch loop hands it to
        # xprof.note_dispatch, which makes it the timeline's h2d leg and
        # the tracer's device.pack span.  None while both are off (the
        # pack pays no clock calls then)
        self.pack_t0: Optional[float] = None
        self.pack_dur: Optional[float] = None
        self.pack_cpu: Optional[float] = None

    def pack(self, arena: np.ndarray, offsets: np.ndarray,
             lengths: np.ndarray, lane: Optional[int] = None):
        """Pack rows into this slot's buffers; records padding waste and
        feeds the auto-tuner (per chip lane when the dispatching worker is
        lane-bound — loongmesh keys the tuner's floors per chip so one
        sparse chip cannot shrink every lane's geometry)."""
        traced = trace.is_active()
        if traced or xprof.is_active():
            self.pack_t0 = time.perf_counter()
            cpu0 = time.thread_time() if traced else None
            batch = pack_rows(arena, offsets, lengths, self.L, self.B,
                              out=(self.rows, self.lengths, self.origins))
            self.pack_cpu = time.thread_time() - cpu0 if traced else None
            self.pack_dur = time.perf_counter() - self.pack_t0
        else:
            self.pack_t0 = self.pack_dur = self.pack_cpu = None
            batch = pack_rows(arena, offsets, lengths, self.L, self.B,
                              out=(self.rows, self.lengths, self.origins))
        self._ring.record_pack(self.B, self.L, batch.n_real,
                               int(np.asarray(lengths, np.int64).sum()),
                               lane=lane)
        return batch

    def nbytes(self) -> int:
        """Host bytes this slot stages for H2D (rows + lengths + origins)
        — the unit the ``ring_slots`` device-memory family accounts in."""
        return self.rows.nbytes + self.lengths.nbytes + self.origins.nbytes

    def release(self) -> None:
        if not self._leased:
            return
        self._leased = False
        self._ring._return(self)

    def __del__(self):
        # ledger backstop: a leased slot dropped without release() belongs
        # to an abandoned dispatch (the DeviceFuture finaliser already
        # warns about that path) — keep the lease count truthful so the
        # storm conservation assertions measure real leaks, not GC noise
        try:
            if self._leased:
                self._leased = False
                self._ring._forget(self)
        except Exception:  # noqa: BLE001 — never raise from a finaliser
            pass


class BatchRing:
    """Geometry-keyed pools of reusable BatchSlots plus the padding-waste
    ledger.  ``lease()`` never blocks: past the per-geometry pool cap it
    hands out a transient slot (dropped on release) — back-pressure is the
    DevicePlane byte budget's job, the ring only recycles memory."""

    def __init__(self, slots_per_geometry: Optional[int] = None):
        self._lock = threading.Lock()
        self._pools: Dict[Tuple[int, int], List[BatchSlot]] = {}
        self._stats: Dict[Tuple[int, int], _GeometryStats] = {}
        self._leased = 0
        self._slots_per_geometry = slots_per_geometry

    def _cap(self) -> int:
        if self._slots_per_geometry is not None:
            return self._slots_per_geometry
        return stream_depth() + 2

    def lease(self, B: int, L: int) -> BatchSlot:
        with self._lock:
            pool = self._pools.get((B, L))
            slot = pool.pop() if pool else None
            self._leased += 1
            st = self._stats.setdefault((B, L), _GeometryStats())
            if slot is None:
                st.slot_allocs += 1
            else:
                st.slot_reuses += 1
        if slot is None:
            slot = BatchSlot(self, B, L)
        slot._leased = True
        # loongxprof device-memory ledger: a leased slot's bytes are live
        # staging until the dispatch that used it materialises — the
        # conservation residual at quiesce checks live==0 once every
        # lease returned (pooled slots are idle host buffers, not leases)
        from .device_plane import mem_note_alloc
        mem_note_alloc("ring_slots", slot.nbytes())
        return slot

    def _return(self, slot: BatchSlot) -> None:
        with self._lock:
            self._leased = max(0, self._leased - 1)
            pool = self._pools.setdefault((slot.B, slot.L), [])
            if len(pool) < self._cap():
                pool.append(slot)
        from .device_plane import mem_note_free
        mem_note_free("ring_slots", slot.nbytes())

    def _forget(self, slot: BatchSlot) -> None:
        """A leased slot died un-released (finaliser backstop)."""
        with self._lock:
            self._leased = max(0, self._leased - 1)
        from .device_plane import mem_note_free
        mem_note_free("ring_slots", slot.nbytes())

    def record_pack(self, B: int, L: int, n_real: int,
                    real_bytes: int, lane: Optional[int] = None) -> None:
        total_bytes = B * L
        padded_bytes = max(0, total_bytes - real_bytes)
        with self._lock:
            st = self._stats.setdefault((B, L), _GeometryStats())
            st.packs += 1
            st.real_rows += n_real
            st.padded_rows += B - n_real
            st.real_bytes += real_bytes
            st.padded_bytes += padded_bytes
        frac = padded_bytes / total_bytes if total_bytes else 0.0
        padding_fraction_histogram().observe(frac)
        rec = _geometry_record(B, L)
        rec.counter("batch_rows_real_total").add(n_real)
        rec.counter("batch_rows_padded_total").add(B - n_real)
        rec.counter("batch_bytes_real_total").add(real_bytes)
        rec.counter("batch_bytes_padded_total").add(padded_bytes)
        auto_tuner().observe_pack(L, B, n_real, lane=lane)

    # -- observability ------------------------------------------------------

    def leased_total(self) -> int:
        with self._lock:
            return self._leased

    def pooled_total(self) -> int:
        with self._lock:
            return sum(len(p) for p in self._pools.values())

    def stats(self) -> Dict[str, dict]:
        """Per-geometry padding/reuse ledger, keyed "BxL"."""
        with self._lock:
            return {f"{B}x{L}": st.as_dict()
                    for (B, L), st in sorted(self._stats.items())}

    def totals(self) -> dict:
        with self._lock:
            real_b = sum(s.real_bytes for s in self._stats.values())
            pad_b = sum(s.padded_bytes for s in self._stats.values())
            return {
                "leased": self._leased,
                "pooled": sum(len(p) for p in self._pools.values()),
                "packs": sum(s.packs for s in self._stats.values()),
                "real_rows": sum(s.real_rows for s in self._stats.values()),
                "padded_rows": sum(s.padded_rows
                                   for s in self._stats.values()),
                "real_bytes": real_b,
                "padded_bytes": pad_b,
                "padding_fraction": (pad_b / (real_b + pad_b)
                                     if real_b + pad_b else 0.0),
            }


_ring: Optional[BatchRing] = None
_ring_lock = threading.Lock()


def batch_ring() -> BatchRing:
    global _ring
    if _ring is None:
        with _ring_lock:
            if _ring is None:
                _ring = BatchRing()
    return _ring


# ---------------------------------------------------------------------------
# width auto-tuner


class _BucketState:
    __slots__ = ("floor", "ewma_pad", "packs_since", "packs_total")

    def __init__(self) -> None:
        self.floor = MIN_BATCH
        self.ewma_pad = 0.0
        self.packs_since = 0
        self.packs_total = 0


class WidthAutoTuner:
    """Runtime batch-geometry and flush-deadline policy.

    * **B floors**: per length bucket L, the padded batch size floor starts
      at the static ``MIN_BATCH`` and walks down by powers of two (never
      below ``MIN_TUNED_FLOOR``) while the observed ROW padding fraction
      ``(B - n_real) / B`` stays high — sparse traffic stops paying for
      256-row tensors that carry 8 real rows.  It walks back up when
      batches run row-dense.  Row occupancy, not byte occupancy, drives
      the decision: the zero tail inside a real row is the L bucket's
      geometry cost (a dense batch of 50-byte lines in the 128 bucket
      must NOT shrink B); the byte view stays observable through the
      ``device_batch_padding_fraction`` histogram.  Movement is
      hysteretic (one step per ``ADJUST_EVERY`` packs) so the jit geometry
      cache sees at most a handful of shapes per bucket.
    * **flush deadline**: how long a worker lane lets a pending batch ride
      the ring before force-completing it.  When the device-utilization
      accounting reports ``device_idle_while_backlogged_ms`` growing (the
      host cannot feed the device), the deadline stretches — deeper
      effective overlap; when the device keeps up it decays back toward
      the default so latency stays interactive.
    """

    ADJUST_EVERY = 32        # packs per floor step (hysteresis)
    HIGH_PAD = 0.5           # shrink the floor above this EWMA
    LOW_PAD = 0.05           # re-grow the floor below this EWMA
    EWMA_ALPHA = 0.125

    DEADLINE_DEFAULT_S = 0.020
    DEADLINE_MAX_S = 0.100

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # keyed (lane, L): lane None is the process-global stream; chip
        # lanes (loongmesh) get their own floors so one sparse chip's
        # traffic cannot shrink the geometry every other chip dispatches;
        # fused pipeline programs (loongresident) key their floors per
        # program as "fused:<sig>" pseudo-lanes — a sparse fused pipeline
        # must not shrink the staged plane's geometry (or vice versa)
        self._buckets: Dict[Tuple[Optional[int], int], _BucketState] = {}
        self._flush_deadline_s = self.DEADLINE_DEFAULT_S
        self._last_adjust = 0.0
        # None = unarmed: the first look at the plane only records the
        # baseline — a tuner created next to a long-lived plane must not
        # charge the plane's lifetime idle history to its first period
        # (the same retroactive-charging shape note_backlogged guards)
        self._last_idle_ms: Optional[float] = None
        self._deadline_adjusts = 0

    # -- B floor ------------------------------------------------------------

    def min_batch_for(self, L: int, lane: Optional[int] = None) -> int:
        if not tuner_enabled():
            return MIN_BATCH
        with self._lock:
            st = self._buckets.get((lane, L))
            return st.floor if st is not None else MIN_BATCH

    def observe_pack(self, L: int, B: int, n_real: int,
                     lane: Optional[int] = None) -> None:
        # row occupancy, deliberately NOT bytes: see the class docstring
        frac = (B - n_real) / B if B else 0.0
        with self._lock:
            st = self._buckets.setdefault((lane, L), _BucketState())
            st.packs_total += 1
            st.packs_since += 1
            st.ewma_pad += self.EWMA_ALPHA * (frac - st.ewma_pad)
            if not tuner_enabled() or st.packs_since < self.ADJUST_EVERY:
                return
            st.packs_since = 0
            if st.ewma_pad > self.HIGH_PAD and st.floor > MIN_TUNED_FLOOR:
                st.floor //= 2
            elif st.ewma_pad < self.LOW_PAD and st.floor < MIN_BATCH:
                st.floor *= 2

    # -- flush deadline -------------------------------------------------------

    def flush_deadline_s(self) -> float:
        return self._flush_deadline_s

    def maybe_adjust(self) -> None:
        """Periodic (≥1 s apart) deadline adjustment off the device plane's
        utilization accounting.  Observe-only: never constructs a plane."""
        if not tuner_enabled():
            return
        now = time.monotonic()
        with self._lock:
            if now - self._last_adjust < 1.0:
                return
            self._last_adjust = now
        from .device_plane import DevicePlane
        plane = DevicePlane._instance
        if plane is None:
            return
        idle_ms = plane.utilization()["idle_while_backlogged_ms"]
        with self._lock:
            if self._last_idle_ms is None:
                self._last_idle_ms = idle_ms    # arm the window only
                return
            delta = idle_ms - self._last_idle_ms
            self._last_idle_ms = idle_ms
            if delta > 25.0:
                # the device starved while the host had backlog: let
                # batches ride the ring longer (more overlap in flight)
                self._flush_deadline_s = min(
                    self._flush_deadline_s * 2.0, self.DEADLINE_MAX_S)
                self._deadline_adjusts += 1
            elif self._flush_deadline_s > self.DEADLINE_DEFAULT_S:
                # device kept up this period: decay back toward the
                # latency-friendly default
                self._flush_deadline_s = max(
                    self._flush_deadline_s / 2.0, self.DEADLINE_DEFAULT_S)
                self._deadline_adjusts += 1

    # -- observability ------------------------------------------------------

    def chosen(self) -> dict:
        """The tuner's current decisions — /debug/status records these so
        every geometry the auto-tuner picked is auditable."""
        def _bucket(st: _BucketState) -> dict:
            return {"floor": st.floor,
                    "ewma_row_padding_fraction": round(st.ewma_pad, 4),
                    "packs": st.packs_total}

        with self._lock:
            lanes: Dict[str, dict] = {}
            glob: Dict[str, dict] = {}
            # lane keys mix int chip indices with "fused:<sig>" program
            # pseudo-lanes (loongresident): chip lanes sort numerically
            # first, pseudo-lanes after them lexicographically
            def _lane_sort(kv):
                lane_k, L_k = kv[0]
                return (lane_k is not None, isinstance(lane_k, str),
                        lane_k if isinstance(lane_k, int) else -1,
                        str(lane_k), L_k)

            for (lane, L), st in sorted(self._buckets.items(),
                                        key=_lane_sort):
                if lane is None:
                    glob[str(L)] = _bucket(st)
                else:
                    lanes.setdefault(str(lane), {})[str(L)] = _bucket(st)
            out = {
                "enabled": tuner_enabled(),
                "flush_deadline_ms": round(self._flush_deadline_s * 1e3, 3),
                "deadline_adjusts": self._deadline_adjusts,
                "buckets": glob,
            }
            if lanes:
                out["lane_buckets"] = lanes
            return out


_tuner: Optional[WidthAutoTuner] = None
_tuner_lock = threading.Lock()


def auto_tuner() -> WidthAutoTuner:
    global _tuner
    if _tuner is None:
        with _tuner_lock:
            if _tuner is None:
                _tuner = WidthAutoTuner()
    return _tuner


def reset_for_testing() -> None:
    """Fresh ring + tuner (tests must not inherit another test's floors,
    deadlines or padding ledger)."""
    global _ring, _tuner
    with _ring_lock:
        _ring = BatchRing()
    with _tuner_lock:
        _tuner = WidthAutoTuner()


# ---------------------------------------------------------------------------
# the pipelined dispatch window


class Chunk:
    """One dispatch riding the window: the caller's tag, the packed batch
    and its ring slot, the future, and the bare kernel it was submitted on
    (a recovery re-run must not pass the dispatch-side fault gates again).
    ``t_advance`` is stamped when its materialisation starts."""

    __slots__ = ("tag", "batch", "slot", "fut", "kernel", "nbytes",
                 "unpack", "t_advance")

    def __init__(self, tag, batch, slot, fut, kernel, nbytes, unpack=None):
        self.tag = tag
        self.batch = batch
        self.slot = slot
        self.fut = fut
        self.kernel = kernel
        self.nbytes = nbytes
        #: set for a chunk submitted on a packed entry: splits its one
        #: output array back into the kernel's tuple (ops/packed_io.py)
        self.unpack = unpack
        self.t_advance = 0.0


def _deliver_error(chunk: Chunk, exc: BaseException):
    """Default recovery: the fault IS the chunk's entry, in its position."""
    return exc


class DeviceStream:
    """THE in-flight chunk ring: ordered pipelined dispatch over a
    DevicePlane.  The regex engine's PendingParse and the fused plane's
    FusedDispatch each own one; nothing else keeps chunks in flight.

    The window owns ring order and every release:

    * ``admit`` — the lane gate (a lane whose breaker is OPEN, or whose
      half-open probe is already in flight, makes the caller respill the
      chunk), then room: a full window first materialises its OLDEST chunk,
      and a lane holding more than its share of the plane budget drains
      its own oldest — one slow chip backs up its own lane only.
    * ``submit_rows`` — geometry (length bucket, tuner floor, the kernel's
      batch multiple), ``ring.lease`` / ``slot.pack`` / ``plane.submit``
      of the ``h2d_gated`` call: the kernel's packed entry on the slot's
      one array where the callable offers one (``packed_call`` /
      ``unpack``, ops/packed_io.py — one transfer in, one copy back),
      else the callable on ``(rows, lengths)``.  When the budget would
      block, the ``on_wait`` hook materialises this window's own oldest
      chunk — never sleep in submit while owning the budget you wait for.
      The slot returns if pack or submit raises.
    * ``advance`` — in submit order; each chunk's copy back started at its
      dispatch (``DevicePlane.submit``), so the advance finds the outputs
      on the host, and splits a packed chunk's one array back into the
      kernel's tuple before ``deliver``.  One ``finally`` returns slot,
      budget and lane bytes, whatever the chunk's fate.
    * ``abandon`` — the cleanup of a dispatch or a drain that failed:
      every pending chunk gives back budget, lane bytes, a held half-open
      probe (no health sample) and its slot.  The round-5 budget leak was
      one missing copy of exactly this.

    The owner says what differs, as arguments: ``program`` (the timeline
    tag), ``lane`` (its chip lane, if bound), ``tuner_key`` (whose
    geometry floors an unbound dispatch moves), ``advance_point`` (the
    chaos point evaluated per advance), and three callbacks —

    ``recover(chunk, exc)`` for a chunk whose materialisation raised:
    return its outputs from another path, return None when the recovery
    wrote the results itself, or raise.  The window reports the lane's
    breaker from that, once: an injected async-stage fault
    (``chaos.ChaosFault``) says nothing about the chip, so a recovery that
    returns is the probe's success and one that raises is inconclusive; a
    ``ChipLaneFault`` or a real failure is the chip's own (failure, and a
    ``ChipLaneFault`` is also counted as respilled rows).  Without a
    callback the fault is delivered as the chunk's entry: it costs one
    batch, never the ring.
    ``deliver(chunk, outputs)`` writes outputs into the owner's buffers
    (the slot may be repacked the moment it returns, so this runs before
    the release); by default entries collect as ``(tag, outputs)`` for
    ``drain()`` to return.
    ``settled(chunk)`` runs once per submitted chunk after its releases,
    for accounting the owner opened at submit.
    """

    def __init__(self, plane=None, depth: Optional[int] = None, *,
                 program: str = "stream", lane=None, tuner_key=None,
                 advance_point: str = FP_RING_ADVANCE,
                 recover=None, deliver=None, settled=None):
        if plane is None:
            from .device_plane import DevicePlane
            plane = DevicePlane.instance()
        self.plane = plane
        self.depth = max(1, depth if depth is not None else stream_depth())
        self.program = program
        self.lane = lane
        self._lane_count = chip_lanes.router().lane_count() \
            if lane is not None else 0
        # loongmesh keys the tuner's floors per chip; an unbound owner
        # may name a pseudo-lane of its own
        self._tuner_key = lane.index if lane is not None else tuner_key
        self._advance_point = advance_point
        self._recover = recover or _deliver_error
        self._deliver = deliver or self._collect
        self._settled = settled
        self._window: deque = deque()
        self._results: List[Tuple[object, object]] = []
        self.advances = 0

    def inflight(self) -> int:
        return len(self._window)

    # -- dispatch -----------------------------------------------------------

    def admit(self, n_rows: int) -> bool:
        """Gate and room for the next chunk.  False: this chip is sick —
        the caller respills the chunk's rows (counted on the lane here);
        events still flow, in order, and the other lanes never notice."""
        lane = self.lane
        if lane is not None and not lane.breaker.allow_probe():
            lane.note_respill(n_rows)
            return False
        while len(self._window) >= self.depth:
            self.advance()
        while lane is not None and self._window \
                and lane.over_share(self.plane, self._lane_count):
            self.advance()
        return True

    def pack(self, arena: np.ndarray, offsets: np.ndarray,
             lengths: np.ndarray, multiple_of: int = 1):
        """Choose the geometry, lease a ring slot and pack the rows into
        it.  Returns ``(slot, batch)``; the slot is the caller's to
        release (``submit`` takes it over)."""
        n = len(offsets)
        L = pick_length_bucket(int(lengths.max()) if n else 1) \
            or LENGTH_BUCKETS[-1]
        key = self._tuner_key
        B = pad_batch(n, min_batch=auto_tuner().min_batch_for(L, key),
                      multiple_of=multiple_of)
        slot = batch_ring().lease(B, L)
        try:
            return slot, slot.pack(arena, offsets, lengths, lane=key)
        except BaseException:
            slot.release()
            raise

    def submit_rows(self, call, arena: np.ndarray, offsets: np.ndarray,
                    lengths: np.ndarray, tag=None, kernel=None) -> Chunk:
        """Pack the rows into a ring slot and dispatch ``call`` on them.
        ``kernel`` is the bare kernel behind ``call`` (what a recovery
        re-runs, and whose ``batch_multiple`` sizes the slot).  A
        ``call`` that offers a packed entry is dispatched through it:
        a gated, lane-placed, sharded or overriding callable offers
        none and gets ``(rows, lengths)``."""
        slot, batch = self.pack(arena, offsets, lengths,
                                getattr(kernel, "batch_multiple", 1))
        packed = getattr(call, "packed_call", None)
        if packed is None:
            return self.submit(call, (batch.rows, batch.lengths),
                               batch.rows.nbytes, tag=tag, slot=slot,
                               batch=batch, bare=kernel)
        return self.submit(packed, (slot.packed,), batch.rows.nbytes,
                           tag=tag, slot=slot, batch=batch,
                           bare=kernel or call, unpack=call.unpack)

    def submit(self, kernel, args, nbytes: int, tag=None,
               slot: Optional[BatchSlot] = None, batch=None,
               bare=None, unpack=None) -> Chunk:
        """Dispatch under the plane budget, advancing first if the window
        is full.  When ``slot`` is given the stream owns its release (at
        materialisation, success or error — including a failure in the
        pre-submit advance, which would otherwise strand the new slot)."""
        try:
            while len(self._window) >= self.depth:
                self.advance()
            fut = self.plane.submit(h2d_gated(kernel), args, nbytes,
                                    on_wait=self._advance_if_any)
        except BaseException:
            if slot is not None:
                slot.release()
            raise
        chunk = Chunk(tag, batch, slot, fut, bare or kernel, nbytes, unpack)
        self._window.append(chunk)
        if slot is not None:
            geometry, t0, dur, cpu = f"{slot.B}x{slot.L}", slot.pack_t0, \
                slot.pack_dur, slot.pack_cpu
        else:
            geometry, t0, dur, cpu = "-", None, None, None
        xprof.note_dispatch(fut, self.program, geometry, t0, dur, cpu)
        lane = self.lane
        if lane is not None:
            if batch is not None:
                lane.note_pack(slot.B, batch.n_real)
            lane.note_dispatch(nbytes)
        return chunk

    def _advance_if_any(self) -> bool:
        """Budget-wait hook: materialise our oldest in-flight chunk so the
        bytes we hold are released while we wait (DevicePlane._acquire's
        deadlock-freedom rule)."""
        if not self._window:
            return False
        self.advance()
        return True

    # -- materialisation ----------------------------------------------------

    def advance(self):
        """Materialise the oldest in-flight chunk (the ring advance) and
        deliver it.  A fault is the owner's ``recover`` to answer; the
        window keeps its order and slot, budget and lane bytes always
        return."""
        if not self._window:
            return None
        chunk = self._window.popleft()
        self.advances += 1
        chunk.t_advance = time.perf_counter()
        try:
            try:
                chaos.faultpoint(self._advance_point)
                out = chunk.fut.result()
                if chunk.unpack is not None:
                    out = chunk.unpack(out[0])
            except Exception as e:  # noqa: BLE001 — the owner's recovery
                chunk.fut.release()
                out = self._recovered(chunk, e)
            else:
                if self.lane is not None:
                    # healthy materialisation on this chip: breaker sample
                    # (re-closes a half-open lane when this was the probe)
                    self.lane.breaker.on_success()
            if out is not None:
                self._deliver(chunk, out)
        finally:
            self._settle(chunk)
        return out

    def _recovered(self, chunk: Chunk, exc: Exception):
        """Run the owner's recovery and tell the lane's breaker how the
        chunk ended — exactly once, or a chunk holding the half-open probe
        wedges its slot and the lane respills for probe_timeout_s."""
        lane = self.lane
        if lane is None:
            return self._recover(chunk, exc)
        chip_fault = isinstance(exc, chip_lanes.ChipLaneFault)
        injected = isinstance(exc, chaos.ChaosFault) and not chip_fault
        out = exc
        try:
            out = self._recover(chunk, exc)
            return out
        finally:
            if not injected:
                lane.breaker.on_failure()
                lane.note_fault()
                if chip_fault:
                    lane.note_respill(int(chunk.batch.n_real))
            elif out is not exc:
                lane.breaker.on_success()
            else:
                lane.breaker.on_inconclusive()

    def _collect(self, chunk: Chunk, out) -> None:
        self._results.append((chunk.tag, out))

    def _settle(self, chunk: Chunk) -> None:
        """Give back everything a chunk holds: budget (a no-op once
        ``result()`` returned it), lane bytes, then the slot — last,
        because it may be repacked the moment it returns to the ring."""
        try:
            chunk.fut.release()
            if self.lane is not None:
                self.lane.note_done(chunk.nbytes)
        finally:
            if chunk.slot is not None:
                chunk.slot.release()
            if self._settled is not None:
                self._settled(chunk)

    def abandon(self) -> None:
        """Release every pending chunk unmaterialised: the owner gives
        this dispatch up (a pack, a submit or another chunk's recovery
        raised) and nobody will ask for them.  A chunk may hold its lane's
        half-open probe — freed with no health sample."""
        while self._window:
            chunk = self._window.popleft()
            try:
                if self.lane is not None:
                    self.lane.breaker.on_inconclusive()
            finally:
                self._settle(chunk)

    def drain(self) -> List[Tuple[object, object]]:
        """Advance until the window empties; returns (and clears) what the
        default ``deliver`` collected, in submit order.  A chunk whose
        recovery raises abandons the rest before the error leaves."""
        try:
            while self._window:
                self.advance()
        except BaseException:
            self.abandon()
            raise
        out, self._results = self._results, []
        return out

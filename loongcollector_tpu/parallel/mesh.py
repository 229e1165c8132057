"""Multi-chip scale-out of the parse data plane (loongmesh).

Reference reality (SURVEY.md §2.7, §5.8): LoongCollector agents are
independent processes — no NCCL/MPI; its parallelism is pipelined threads +
per-pipeline queues.  The TPU-native equivalent for one host owning multiple
chips: **data-parallel sharding of event batches over an ICI-connected
device mesh**.  Events are embarrassingly parallel, so the batch dimension
shards cleanly; the only cross-chip communication is tiny psum'd telemetry
(match counts / byte counts for the self-monitor), which rides ICI.

Design: `shard_map` over a 1-D ('dp',) mesh; each chip runs the same
gather-free extraction kernel on its batch shard; jax.lax.psum aggregates
stats.  Multi-host (DCN) follows the same SPMD program — jax.distributed
initialises the global mesh and the batch dimension spans hosts; no code
change in the kernel.

loongmesh (ISSUE 9) promoted :class:`ShardedKernel` from a bench adapter
into the production dispatch path:

* batches arrive **shard-aligned**: the engine packs into batch-ring slots
  whose B is already a mesh multiple (``ShardedKernel.batch_multiple``
  feeds ``pad_batch(multiple_of=...)``), so the hot path never pays the
  old host-side ``np.concatenate`` copy.  Direct callers with odd B fall
  back to a kernel-private persistent pad buffer (same
  zero-the-tail-in-place discipline as a BatchRing slot, without entering
  the ring's lease ledger).
* dispatch goes through a **donated** sharded step where the backend
  supports donation: each call's inputs are transient per-shard staging
  copies, so XLA reuses their HBM for the outputs — DMA of batch N+1
  overlaps compute of N on every chip.
* the psum'd telemetry no longer dies on device: per-dispatch stats are
  queued and folded — off the hot path — into the process metrics
  (``mesh_matched_total`` / ``mesh_events_total`` / ``mesh_bytes_total``,
  labelled by chip count) plus per-chip row-occupancy accounting, all
  surfaced in ``/debug/status`` (monitor/exposition.collect_status).

``LOONG_MESH_CHIPS`` caps the mesh width (the bench chips=1/2/4/8 sweep's
knob); per-chip *lanes* — affinity, breakers, chaos — live in
ops/chip_lanes.py.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import xprof
from ..ops.compile_watch import watched_jit
from ..ops.device_plane import mem_note_alloc, mem_note_free
from ..ops.regex.program import SegmentProgram
from ..ops.kernels.field_extract import build_extract_fn, donation_supported


def make_mesh(n_devices: Optional[int] = None, axis: str = "dp") -> Mesh:
    devs = jax.devices()
    if n_devices is None:
        from ..ops.chip_lanes import mesh_chip_cap
        n_devices = mesh_chip_cap()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis,))


class ShardedParsePlane:
    """The parse step jitted over a device mesh.

    step(rows [B,L], lengths [B]) ->
        ok [B] bool, cap_off [B,C] i32, cap_len [B,C] i32,
        stats {matched, events, bytes} — psum-replicated across the mesh.

    B must be divisible by the mesh size (the batch builder pads to a mesh
    multiple; see ShardedKernel.batch_multiple).
    """

    def __init__(self, program: SegmentProgram, mesh: Optional[Mesh] = None):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.program = program
        extract = build_extract_fn(program)
        axis = self.mesh.axis_names[0]

        def _local_step(rows, lengths):
            ok, off, length = extract(rows, lengths)
            stats = {
                "matched": jax.lax.psum(jnp.sum(ok.astype(jnp.int32)), axis),
                "events": jax.lax.psum(
                    jnp.sum((lengths > 0).astype(jnp.int32)), axis),
                "bytes": jax.lax.psum(jnp.sum(lengths), axis),
            }
            return ok, off, length, stats

        sharded = jax.shard_map(
            _local_step, mesh=self.mesh,
            in_specs=(P(axis, None), P(axis)),
            out_specs=(P(axis), P(axis, None), P(axis, None),
                       {"matched": P(), "events": P(), "bytes": P()}))
        self._fn = watched_jit(sharded, "sharded_parse")
        # donated variant (loongmesh): inputs are per-dispatch staging
        # copies produced by put(), so XLA may alias their per-shard HBM
        # for the outputs.  CPU ignores donation with a per-call warning,
        # so the variant only exists where donation is real.
        self._fn_donated = (watched_jit(sharded, "sharded_parse",
                                        donate_argnums=(0, 1))
                            if donation_supported() else None)
        ax = axis
        self._in_shardings = (NamedSharding(self.mesh, P(ax, None)),
                              NamedSharding(self.mesh, P(ax)))

    def put(self, rows: np.ndarray, lengths: np.ndarray):
        """Device-put host arrays with the mesh sharding (one shard per
        chip's HBM)."""
        return (jax.device_put(rows, self._in_shardings[0]),
                jax.device_put(lengths, self._in_shardings[1]))

    def __call__(self, rows, lengths):
        return self._fn(rows, lengths)

    def donated(self, rows_d, lengths_d):
        """The donating step (falls back to the plain step off-TPU/GPU).
        Only safe for device buffers the caller will never touch again —
        put() copies qualify, a bench loop's reused device input does
        not."""
        if self._fn_donated is None:
            return self._fn(rows_d, lengths_d)
        return self._fn_donated(rows_d, lengths_d)

    @property
    def num_devices(self) -> int:
        return self.mesh.size


# ---------------------------------------------------------------------------
# mesh telemetry: psum'd stats materialised OFF the hot path


_mesh_records: Dict[int, object] = {}
_mesh_records_lock = threading.Lock()


def _mesh_record(chips: int):
    rec = _mesh_records.get(chips)
    if rec is None:
        with _mesh_records_lock:
            rec = _mesh_records.get(chips)
            if rec is None:
                from ..monitor.metrics import MetricsRecord
                rec = MetricsRecord(
                    category="device_plane",
                    labels={"component": "mesh", "chips": str(chips)})
                _mesh_records[chips] = rec
    return rec


_live_kernels: "weakref.WeakSet" = weakref.WeakSet()


def mesh_status() -> Optional[dict]:
    """Aggregate status of every live ShardedKernel (the /debug/status
    ``mesh.kernels`` section).  Folds any queued psum stats first — the
    status page is exactly the off-hot-path materialisation point the
    telemetry queue exists for.  None when the process never built one."""
    kernels = list(_live_kernels)
    if not kernels:
        return None
    out = []
    for k in kernels:
        try:
            out.append(k.status())
        except Exception:  # noqa: BLE001 — status must never 500
            pass
    return {"kernels": out} if out else None


class ShardedKernel:
    """Engine-facing adapter: makes ShardedParsePlane shaped like the
    single-device extract kernels (rows, lengths) → (ok, off, len), so the
    regex engine's async dispatch path (DevicePlane budget + watermark
    back-pressure + batch-ring slots) drives the whole mesh without
    special cases.

    The engine consults :attr:`batch_multiple` when sizing the slot, so
    production batches arrive already mesh-aligned and dispatch is
    copy-free; an unaligned direct call pads through a kernel-private
    persistent buffer (tail zeroed in place — never ``np.concatenate``).

    Telemetry: every dispatch queues its psum'd device stats; the queue is
    folded into the ``mesh_*_total`` counters off the hot path — at status
    collection (:func:`mesh_status`), via :meth:`materialize_stats`, or
    lazily when the queue outgrows the pipeline depth (the oldest entry's
    compute has long finished by then, so np.asarray is a cheap copy, not
    a device wait).  ``last_stats`` keeps the most recent dispatch's
    on-device handle for tests and ad-hoc inspection."""

    #: the ``watched_jit`` family the mesh step compiles under
    family = "sharded_parse"

    #: fold queued stats once the backlog exceeds this many dispatches —
    #: deeper than any stream depth, so the fold never blocks on compute
    STATS_QUEUE_MAX = 8

    def __init__(self, program: SegmentProgram, mesh: Optional[Mesh] = None):
        self.plane = ShardedParsePlane(program, mesh)
        self.last_stats = None
        # serializes the host-side staging of one dispatch (pad-buffer
        # reuse + per-chip accounting + device_put): multiple unbound
        # workers (LOONG_MESH_LANES=0) share this kernel through the
        # engine cache, and an unlocked numpy += loses updates while a
        # shared pad buffer could be repacked mid-transfer.  Held only
        # until the async dispatch returns — never across materialise.
        self._dispatch_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._stats_pending: deque = deque()
        self._record = _mesh_record(self.plane.num_devices)
        self._matched_total = self._record.counter("mesh_matched_total")
        self._events_total = self._record.counter("mesh_events_total")
        self._bytes_total = self._record.counter("mesh_bytes_total")
        self._dispatches_total = self._record.counter(
            "mesh_dispatches_total")
        self._pad_fallback_total = self._record.counter(
            "mesh_pad_fallback_total")
        # per-chip row occupancy, computed host-side from the lengths
        # array (one reshape + count per dispatch — no extra collective)
        m = self.plane.num_devices
        self._chip_real_rows = np.zeros(m, dtype=np.int64)
        self._chip_rows = np.zeros(m, dtype=np.int64)
        # private pad buffers for unaligned DIRECT calls, keyed (B, L):
        # reused like a one-slot ring without entering the lease ledger
        self._pad_buffers: Dict[tuple, tuple] = {}
        _live_kernels.add(self)

    @property
    def batch_multiple(self) -> int:
        """Engine contract: pack batches whose B is a multiple of this
        (pad rows zeroed in the slot) and dispatch stays copy-free."""
        return self.plane.num_devices

    # -- padding (fallback only: the engine path arrives aligned) -----------

    def _pad_to_mesh(self, rows, lengths):
        m = self.plane.num_devices
        b = rows.shape[0]
        if b % m == 0:
            return rows, lengths
        self._pad_fallback_total.add(1)
        B = b + (m - b % m)
        L = rows.shape[1]
        buf = self._pad_buffers.get((B, L))
        if buf is None:
            buf = (np.zeros((B, L), rows.dtype), np.zeros(B, lengths.dtype))
            self._pad_buffers[(B, L)] = buf
        prows, plens = buf
        prows[:b] = rows
        prows[b:] = 0
        plens[:b] = lengths
        plens[b:] = 0
        return prows, plens

    # -- telemetry -----------------------------------------------------------

    def _note_per_chip(self, lengths: np.ndarray) -> None:
        m = self.plane.num_devices
        per = np.asarray(lengths).reshape(m, -1)
        self._chip_real_rows += (per > 0).sum(axis=1)
        self._chip_rows += per.shape[1]

    def _queue_stats(self, stats) -> None:
        with self._stats_lock:
            self._stats_pending.append(stats)
            overflow = len(self._stats_pending) > self.STATS_QUEUE_MAX
        if overflow:
            self.materialize_stats(max_entries=1)

    def materialize_stats(self, max_entries: Optional[int] = None) -> dict:
        """Fold queued psum stats into the mesh_* counters (np.asarray on
        each entry — blocking only if that dispatch's compute is somehow
        still in flight, which the queue depth guards against on the lazy
        path).  Returns the counters' running totals."""
        while True:
            with self._stats_lock:
                if not self._stats_pending or max_entries == 0:
                    break
                stats = self._stats_pending.popleft()
            if max_entries is not None:
                max_entries -= 1
            try:
                self._matched_total.add(int(np.asarray(stats["matched"])))
                self._events_total.add(int(np.asarray(stats["events"])))
                self._bytes_total.add(int(np.asarray(stats["bytes"])))
            except Exception:  # noqa: BLE001 — a failed dispatch's stats
                pass           # die with it; the counters stay truthful
        return {
            "matched": self._matched_total.value,
            "events": self._events_total.value,
            "bytes": self._bytes_total.value,
        }

    def status(self) -> dict:
        totals = self.materialize_stats()
        rows = self._chip_rows
        real = self._chip_real_rows
        occ = np.divide(real, np.maximum(rows, 1)).round(4)
        return {
            "chips": self.plane.num_devices,
            "dispatches": self._dispatches_total.value,
            "pad_fallbacks": self._pad_fallback_total.value,
            "totals": totals,
            "per_chip_row_occupancy": occ.tolist(),
            "per_chip_padding_fraction":
                (1.0 - occ).round(4).tolist(),
        }

    # -- dispatch ------------------------------------------------------------

    def _dispatch(self, rows, lengths, donate: bool):
        with self._dispatch_lock:
            rows, lengths = self._pad_to_mesh(rows, lengths)
            self._note_per_chip(lengths)
            self._dispatches_total.add(1)
            # loongxprof: this runs INSIDE DevicePlane.submit's kernel
            # call when the engine dispatches the mesh, so the per-shard
            # device_put is the enclosing dispatch's real H2D leg —
            # attached via the current-dispatch TLS.  The staging copies'
            # footprint is ledgered for the duration of the dispatch call
            # (donation hands the same HBM to the outputs after that).
            xid = xprof.current_dispatch()
            staged = rows.nbytes + lengths.nbytes
            mem_note_alloc("sharded_staging", staged)
            try:
                if xid:
                    t_put = time.perf_counter()
                    rows_d, lengths_d = self.plane.put(rows, lengths)
                    xprof.leg(xid, "h2d", t_put,
                              time.perf_counter() - t_put,
                              chips=self.plane.num_devices)
                else:
                    rows_d, lengths_d = self.plane.put(rows, lengths)
                step = self.plane.donated if donate else self.plane
                ok, off, length, stats = step(rows_d, lengths_d)
            finally:
                mem_note_free("sharded_staging", staged)
        self.last_stats = stats
        self._queue_stats(stats)
        return ok, off, length

    def __call__(self, rows, lengths):
        return self._dispatch(rows, lengths, donate=False)

    def donated_call(self, rows, lengths):
        """Streaming-path dispatch (PendingParse picks this up via the
        same ``donated_call`` protocol as the single-chip kernels): the
        put() staging copies are transient, so their per-shard HBM is
        donated to the outputs."""
        return self._dispatch(rows, lengths, donate=True)

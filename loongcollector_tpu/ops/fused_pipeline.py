"""loongresident: single-dispatch pipeline fusion — the AOT stage compiler.

With every device-capable stage running its own pack → H2D → dispatch →
materialise cycle, an N-stage pipeline pays N synchronous device round
trips per batch.  ParPaRaw's whole
contribution is never leaving the device between phases; the DFA
processing literature composes automata passes into one resident
execution.  This module does the same for a pipeline's consecutive
device-capable stages:

* **StageSpec / StageCond** — the declarative resident form of one stage
  (Tier-1 extract, JSON field spans, fused multi-accept scan, structural
  index, filter keep mask, rule-list label).  A filter condition or a
  rule list over a field an earlier stage of the program publishes binds
  to that stage's DEVICE-RESIDENT span columns (``("capture", producer,
  cap)``) — no host bounce, no re-pack between stages.  The contract is
  the columns, not the kind: any stage in ``SPAN_STAGES`` publishes ``(ok[B], off[B, C], len[B, C])`` as
  its first three outputs (row-relative offsets, length −1 where the
  capture is absent), and that is all a ``span_match`` or a ``label``
  reads.

* **FusedProgramKernel** — ONE jitted program per (stage list, B, L)
  geometry composed from the existing kernel cores
  (``build_extract_fn`` / ``build_fused_scan_fn`` / ``build_index_fn`` /
  ``build_dfa_match_fn`` / ``build_dfa_span_match_fn`` /
  ``build_span_label_fn`` / ``build_json_fields_fn``): inputs packed
  once, inter-stage columns stay in HBM, every stage's outputs
  materialise together in one D2H.  ``packed_call`` is the streaming
  path's entry (ops/packed_io.py): one array in, one array out.

* **FusedDispatch** — the dispatch handle riding the EXISTING machinery:
  batch-ring slots (no allocator churn), the DevicePlane byte budget with
  the never-sleep-owning-budget drain rule, ≤ depth chunks in flight
  (loongstream window), WidthAutoTuner floors keyed per fused program
  (``("fused", sig)`` pseudo-lane buckets; a real chip lane's per-chip
  floors win on mesh hosts), chip-lane placement via the engine's
  ``_LanePlacedKernel``.  Per-chunk fault isolation DEMOTES a failing
  chunk to the per-stage dispatch path (each member stage's own kernel,
  separate dispatches) — events are never lost; demotions are counted
  (``fused_demotions_total``) and alarmed once per program.

* **Program cache** — content-addressed like the DFA cache: in-process
  LRU keyed by the sha256 of the stage identity list, plus
  ``<data_dir>/fused_cache/`` plan records persisting the stage list and
  the observed (B, L) geometries so a restart skips plan construction
  (``fused_program_cache_{hit,miss}_total``) and can AOT-warm the jit
  geometries (``LOONG_FUSED_WARM=1``).

Chaos point ``device_plane.fused_dispatch`` faults the materialise edge:
ERROR demotes that one chunk to the per-stage path, DELAY exercises the
ring deadline.  ``stage_fusion_status()`` feeds the /debug/status
``stage_fusion`` section.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import chaos
from . import chip_lanes
from .chip_lanes import ChipLaneFault, lane_gated
from .device_batch import MAX_BATCH
from .device_plane import mem_note_alloc, mem_note_free

FP_FUSED_DISPATCH = chaos.register_point("device_plane.fused_dispatch")

CACHE_VERSION = 1
ENV_FUSED = "LOONG_FUSED"
ENV_WARM = "LOONG_FUSED_WARM"
ENV_CACHE = "LOONG_FUSED_CACHE"

#: flat-output width per stage kind
_STAGE_WIDTH = {"extract": 3, "scan": 1, "struct_index": 4, "keep": 1,
                "json_fields": 6, "label": 1}


def _stage_columns(spec) -> Optional[Tuple]:
    """The forms (ops/packed_io.Columns) of one stage's outputs, or None
    for a stage with an output that is no per-row 32-bit column
    (``struct_index``'s packed words are as wide as the row)."""
    from .packed_io import span_columns
    if spec.kind == "extract":
        return span_columns(spec.payload.num_caps)
    if spec.kind == "json_fields":
        return span_columns(spec.payload.num_caps) \
            + (("i32", None), ("i32", None), ("i32", 2))
    if spec.kind == "scan":
        return (("u32", None),)
    if spec.kind == "keep":
        return (("bool", None),)
    if spec.kind == "label":
        return (("i32", None),)
    return None


#: stage kinds that publish span columns: their first three outputs are
#: ``(ok[B], off[B, C], len[B, C])`` with C = ``payload.num_caps``, and a
#: ``span_match`` condition may bind any of them
SPAN_STAGES = ("extract", "json_fields")


def fusion_enabled() -> bool:
    """Stage fusion routing: ``LOONG_FUSED=1`` forces, ``=0`` disables;
    unset → auto, ON exactly when the engines' own routing default is the
    device tier (an accelerator backend).  In host mode the per-stage
    native walkers already skip the round trips fusion exists to remove,
    so fusing there would only FORCE device dispatches the router proved
    slower."""
    env = os.environ.get(ENV_FUSED)
    if env is not None:
        return env != "0"
    try:
        from .regex.engine import _native_host_mode
        return not _native_host_mode()
    except Exception:  # noqa: BLE001 — no backend ⇒ no fusion
        return False


# ---------------------------------------------------------------------------
# stage model
# ---------------------------------------------------------------------------


class StageCond:
    """One boolean condition of a 'keep' stage (a filter Include/Exclude
    entry in resident form).

    kind: ``match`` (DFA full-match over the source rows), ``extract_ok``
    (Tier-1 program ok bit over the source rows), ``span_match`` (DFA
    full-match over a span column a PRIOR stage publishes, device-resident
    — ``binding=(producer_stage_idx, cap_idx)``; the producer is any stage
    of ``SPAN_STAGES``).  ``staged`` is the
    condition's own kernel for the per-stage demotion path."""

    __slots__ = ("kind", "payload", "binding", "negate", "staged", "ident")

    def __init__(self, kind: str, payload, ident,
                 binding: Optional[Tuple[int, int]] = None,
                 negate: bool = False, staged: Optional[Callable] = None):
        self.kind = kind
        self.payload = payload
        self.binding = binding
        self.negate = negate
        self.staged = staged
        self.ident = ident


class StageSpec:
    """Declarative resident form of one device-capable pipeline stage.

    kind: ``extract`` (Tier-1 segment program → ok + capture spans),
    ``json_fields`` (top-level JSON members → ok + value spans, status,
    member count, key signature), ``scan`` (fused multi-accept automaton →
    tag bitmask), ``struct_index`` (structural bitmaps), ``keep`` (filter
    mask over StageConds), ``label`` (the fused multi-accept automaton of
    an ordered rule list walked once over a span column → index of the
    first rule that matches, −1 for none or an absent span;
    ``binding=(producer_stage_idx, cap_idx)`` names the column, a stage of
    ``SPAN_STAGES``'s, and None the packed rows themselves; ``staged``
    takes ``(rows, lengths, starts, spanlens)``).

    ``ident`` is the canonical content identity (pattern strings, mode)
    the program cache hashes; ``staged`` is the stage's OWN kernel (the
    existing per-stage dispatch path) used when a chunk demotes;
    ``terminal`` marks stages that rebuild the row population (multiline
    classify) and therefore must end a fused run."""

    __slots__ = ("kind", "payload", "ident", "staged", "terminal", "label",
                 "binding")

    def __init__(self, kind: str, payload, ident, staged=None,
                 terminal: bool = False, label: str = "",
                 binding: Optional[Tuple[int, int]] = None):
        self.kind = kind
        self.payload = payload
        self.ident = ident
        self.staged = staged
        self.terminal = terminal
        self.label = label or kind
        self.binding = binding

    @property
    def width(self) -> int:
        return _STAGE_WIDTH[self.kind]


def _bound_span(spec: StageSpec, stage_outs, lengths):
    """``(starts, spanlens)`` of the column a ``label`` stage walks: the
    capture it binds, as the producer left it (device-resident in the
    program, materialised on the per-stage path), or the whole row."""
    if spec.binding is None:
        return lengths * 0, lengths
    prod, cap = spec.binding
    p_off, p_len = stage_outs[prod][1:3]
    return p_off[:, cap], p_len[:, cap]


def build_fused_fn(specs: Sequence[StageSpec]):
    """Compose the member stages' kernel cores into ONE jit-able
    f(rows u8 [B,L], lengths i32 [B]) -> flat tuple of stage outputs.
    Inter-stage values (capture spans feeding span-bound conditions) are
    jnp values — XLA keeps them in HBM; nothing crosses back to the host
    until the caller materialises the flat tuple once."""
    from .kernels.dfa_scan import (build_dfa_match_fn,
                                   build_dfa_span_match_fn,
                                   build_fused_scan_fn, build_span_label_fn)
    from .kernels.field_extract import build_extract_fn
    from .kernels.json_fields import build_json_fields_fn
    from .kernels.struct_index import build_index_fn

    stage_fns: List = []
    for spec in specs:
        if spec.kind == "extract":
            stage_fns.append(build_extract_fn(spec.payload))
        elif spec.kind == "json_fields":
            stage_fns.append(build_json_fields_fn(
                spec.payload.kmax, tuple(spec.payload.bound)))
        elif spec.kind == "scan":
            stage_fns.append(build_fused_scan_fn(spec.payload))
        elif spec.kind == "struct_index":
            mode, sep = spec.payload
            stage_fns.append(build_index_fn(mode, sep))
        elif spec.kind == "label":
            stage_fns.append(build_span_label_fn(spec.payload))
        elif spec.kind == "keep":
            fns = []
            for cond in spec.payload:
                if cond.kind == "match":
                    fns.append(build_dfa_match_fn(cond.payload))
                elif cond.kind == "span_match":
                    fns.append(build_dfa_span_match_fn(cond.payload))
                elif cond.kind == "extract_ok":
                    fns.append(build_extract_fn(cond.payload))
                else:  # pragma: no cover
                    raise AssertionError(cond.kind)
            stage_fns.append(fns)
        else:  # pragma: no cover
            raise AssertionError(spec.kind)

    def fused(rows, lengths):
        stage_outs: List[Tuple] = []
        flat: List = []
        for spec, fn in zip(specs, stage_fns):
            if spec.kind in SPAN_STAGES or spec.kind == "struct_index":
                outs = tuple(fn(rows, lengths))
            elif spec.kind == "scan":
                outs = (fn(rows, lengths),)
            elif spec.kind == "label":
                outs = (fn(rows, lengths,
                           *_bound_span(spec, stage_outs, lengths)),)
            else:  # keep
                keep = None
                for cond, cfn in zip(spec.payload, fn):
                    if cond.kind == "match":
                        # absent named-source rows (length -1) never
                        # match — the staged path's ``ok & src.present``
                        ok = cfn(rows, lengths) & (lengths >= 0)
                    elif cond.kind == "extract_ok":
                        ok = cfn(rows, lengths)[0] & (lengths >= 0)
                    else:  # span_match: the span columns a prior stage
                        # publishes (SPAN_STAGES), still device-resident
                        prod, cap = cond.binding
                        p_off, p_len = stage_outs[prod][1:3]
                        ok = cfn(rows, lengths, p_off[:, cap], p_len[:, cap])
                    if cond.negate:
                        ok = ~ok
                    keep = ok if keep is None else (keep & ok)
                outs = (keep,)
            stage_outs.append(outs)
            flat.extend(outs)
        return tuple(flat)

    return fused


# ---------------------------------------------------------------------------
# the compiled program
# ---------------------------------------------------------------------------


class FusedProgramKernel:
    """Owns the jitted fused program for one stage list.

    jit caches per (B, L) geometry internally; the dispatcher quantises
    shapes through the device_batch buckets and the tuner's per-program
    floors so each geometry compiles once.  ``dispatch_count`` counts
    fused dispatches — the single-dispatch-per-batch-slot acceptance
    assertion reads it directly.

    Two jitted variants per geometry: ``(rows, lengths)`` → flat tuple
    (``__call__``: lane-placed dispatches, a kernel override, the tests)
    and the packed entry the streaming path and ``warm()`` run
    (``packed_call`` / ``unpack``, ops/packed_io.py).  A program with a
    ``struct_index`` stage has only the first: ``packed_call`` is None."""

    def __init__(self, specs: Sequence[StageSpec], signature: str):
        from .compile_watch import watched_jit
        from .packed_io import packed_entry
        self.specs = list(specs)
        self.signature = signature
        fused = build_fused_fn(self.specs)
        self._fn = watched_jit(fused, "fused_program")
        self._fn_packed = self.packed_call = self.unpack = None
        forms = [_stage_columns(spec) for spec in self.specs]
        if None not in forms:
            self._fn_packed, self.unpack = packed_entry(
                fused, [f for stage in forms for f in stage],
                "fused_program")
            self.packed_call = self._packed_call
        self._lane_kernels: Dict[int, object] = {}
        self._kernel_override = None
        self.dispatch_count = 0
        self.demotions = 0
        self.lane_respills = 0
        self.roundtrip_ms_total = 0.0
        self.idle_attr_ms = 0.0
        self.geometries: set = set()
        #: dispatches by (B, L): which shape the calls of a window had
        self.geometry_dispatches: Dict[Tuple[int, int], int] = {}
        self._geom_dirty = False
        self.layout: List[Tuple[int, int]] = []
        i = 0
        for spec in self.specs:
            self.layout.append((i, spec.width))
            i += spec.width
        self.n_outputs = i

    # -- dispatch entry points ---------------------------------------------

    def __call__(self, rows, lengths):
        self.dispatch_count += 1
        return self._fn(rows, lengths)

    def _packed_call(self, packed):
        self.dispatch_count += 1
        return self._fn_packed(packed)

    def set_kernel_override(self, kern) -> None:
        """Test/bench hook (mirrors RegexEngine.set_device_kernel_override):
        route this program's fused dispatches through ``kern`` — e.g. a
        LatencyInjectedKernel wrapping the jitted program to model a
        remote chip.  None restores normal selection."""
        self._kernel_override = kern

    def for_lane(self, lane):
        """Chip-lane placement (loongmesh): the fused program executes on
        the dispatching worker's home chip through the same placed-kernel
        wrapper the engines use."""
        k = self._lane_kernels.get(lane.index)
        if k is None:
            from .regex.engine import _LanePlacedKernel
            k = _LanePlacedKernel(self, lane)
            self._lane_kernels[lane.index] = k
        return k

    # -- per-stage demotion path -------------------------------------------

    def staged_run(self, rows: np.ndarray, lengths: np.ndarray) -> List:
        """The existing per-stage dispatch path over one packed chunk:
        each member stage's OWN kernel runs as its own dispatch and its
        outputs materialise before the next stage needs them (span-bound
        conditions read the producer's materialised captures).  This is
        the fault-isolation target — dispatch count N instead of 1,
        answers identical; the host pulls between stages here are the
        demotion tier by design."""
        outs: List[Tuple[np.ndarray, ...]] = []
        lens_np = np.asarray(lengths)
        for spec in self.specs:
            if spec.kind == "label":
                # loonglint: disable=host-bounce
                outs.append((np.asarray(spec.staged(
                    rows, lengths, *_bound_span(spec, outs, lens_np))),))
            elif spec.kind != "keep":
                raw = spec.staged(rows, lengths)
                if not isinstance(raw, (tuple, list)):
                    raw = (raw,)
                # demotion tier by design: per-stage dispatches with
                # materialised hand-off IS the per-stage fallback path
                # loonglint: disable=host-bounce
                outs.append(tuple(np.asarray(a) for a in raw))
            else:  # keep
                keep: Optional[np.ndarray] = None
                for cond in spec.payload:
                    if cond.kind == "match":
                        # loonglint: disable=host-bounce
                        ok = np.asarray(cond.staged(rows, lengths)) \
                            & (lens_np >= 0)
                    elif cond.kind == "extract_ok":
                        # loonglint: disable=host-bounce
                        ok = np.asarray(cond.staged(rows, lengths)[0]) \
                            & (lens_np >= 0)
                    else:
                        prod, cap = cond.binding
                        p_off, p_len = outs[prod][1:3]
                        # loonglint: disable=host-bounce
                        ok = np.asarray(cond.staged(
                            rows, lengths, p_off[:, cap], p_len[:, cap]))
                    if cond.negate:
                        ok = ~ok
                    keep = ok if keep is None else (keep & ok)
                outs.append((keep,))
        return outs

    # -- geometry ledger ----------------------------------------------------

    def note_geometry(self, B: int, L: int) -> None:
        self.geometry_dispatches[(B, L)] = \
            self.geometry_dispatches.get((B, L), 0) + 1
        if (B, L) not in self.geometries:
            self.geometries.add((B, L))
            self._geom_dirty = True
            _persist_plan(self)

    def warm(self) -> int:
        """AOT-compile the persisted geometries (restart warm start): the
        first data batch of a known shape then hits a ready executable.
        Warms the packed entry where the program has one — that is the
        jit the steady-state dispatch path actually runs — else the
        tuple one.  Returns the number of geometries compiled."""
        from .packed_io import packed_rows
        n = 0
        for B, L in sorted(self.geometries):
            if self._fn_packed is not None:
                self._fn_packed(
                    np.zeros((packed_rows(B, L), L), dtype=np.uint8))
            else:
                self._fn(np.zeros((B, L), dtype=np.uint8),
                         np.zeros(B, dtype=np.int32))
            n += 1
        return n

    def status(self) -> dict:
        return {
            "signature": self.signature,
            "stages": [s.label for s in self.specs],
            # span columns each stage publishes (0: none) — with the
            # geometries, the shapes of a call's outputs
            "captures": [s.payload.num_caps if s.kind in SPAN_STAGES else 0
                         for s in self.specs],
            "dispatches": self.dispatch_count,
            "demotions": self.demotions,
            "lane_respills": self.lane_respills,
            "geometries": sorted(f"{b}x{l}" for b, l in self.geometries),
            "geometry_dispatches": {
                f"{b}x{l}": n
                for (b, l), n in sorted(self.geometry_dispatches.items())},
            "roundtrip_ms_total": round(self.roundtrip_ms_total, 3),
            "idle_while_backlogged_attr_ms": round(self.idle_attr_ms, 3),
        }


# ---------------------------------------------------------------------------
# content-addressed program cache (mem LRU + <data_dir>/fused_cache/)
# ---------------------------------------------------------------------------

_mem_cache: "OrderedDict[str, FusedProgramKernel]" = OrderedDict()
_mem_cache_lock = threading.Lock()
_MEM_CACHE_MAX = 64
_cache_dir: Optional[str] = None


def set_cache_dir(path: Optional[str]) -> None:
    """Application startup hook (mirrors fuse.set_cache_dir): fused plan
    records persist under ``<data_dir>/fused_cache/``."""
    global _cache_dir
    _cache_dir = path


def _resolved_cache_dir() -> Optional[str]:
    env = os.environ.get(ENV_CACHE)
    if env:
        return env
    return _cache_dir


def program_signature(specs: Sequence[StageSpec]) -> str:
    blob = json.dumps([CACHE_VERSION] + [_jsonable(s.ident) for s in specs],
                      ensure_ascii=False)
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:20]


def _jsonable(ident):
    if isinstance(ident, (list, tuple)):
        return [_jsonable(x) for x in ident]
    return ident


def _plan_path(dirname: str, signature: str) -> str:
    return os.path.join(dirname, "fused_cache",
                        f"v{CACHE_VERSION}_{signature}.json")


def _persist_plan(program: FusedProgramKernel) -> None:
    dirname = _resolved_cache_dir()
    if not dirname or not program._geom_dirty:
        return
    program._geom_dirty = False
    path = _plan_path(dirname, program.signature)
    doc = {
        "version": CACHE_VERSION,
        "stages": [_jsonable(s.ident) for s in program.specs],
        "geometries": sorted([b, l] for b, l in program.geometries),
    }
    tmp = path + f".tmp.{os.getpid()}"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _load_plan(signature: str, specs: Sequence[StageSpec]) -> Optional[dict]:
    dirname = _resolved_cache_dir()
    if not dirname:
        return None
    try:
        with open(_plan_path(dirname, signature), "r",
                  encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if doc.get("version") != CACHE_VERSION:
        return None
    # hash-collision / stale-content guard, like the DFA cache: the stage
    # identity list as given must match the stored plan exactly
    if doc.get("stages") != [_jsonable(s.ident) for s in specs]:
        return None
    return doc


def get_fused_program(specs: Sequence[StageSpec]) -> FusedProgramKernel:
    """The two-level content-addressed cache: in-process LRU (hot-reloads
    reuse compiled programs) and the on-disk plan record (restarts skip
    plan construction and recover the geometry set for AOT warm)."""
    signature = program_signature(specs)
    with _mem_cache_lock:
        got = _mem_cache.get(signature)
        if got is not None:
            _mem_cache.move_to_end(signature)
    if got is not None:
        _count("fused_program_cache_hit_total")
        return got
    plan = _load_plan(signature, specs)
    program = FusedProgramKernel(specs, signature)
    if plan is not None:
        _count("fused_program_cache_hit_total")
        program.geometries = {(int(b), int(l))
                              for b, l in plan.get("geometries", [])}
        if os.environ.get(ENV_WARM) == "1":
            try:
                program.warm()
            except Exception:  # noqa: BLE001 — warm is best-effort
                pass
    else:
        _count("fused_program_cache_miss_total")
        program._geom_dirty = True
        _persist_plan(program)
    with _mem_cache_lock:
        # first-wins on a concurrent miss: every caller must share ONE
        # kernel object or per-program dispatch/demotion accounting (and
        # the jit cache) splits across losers — the aggregator-base
        # lazy-init race shape.  Construction above is cheap (jit
        # compiles lazily at first call), so a discarded loser wastes
        # closures, not a compile.
        existing = _mem_cache.get(signature)
        if existing is not None:
            program = existing
        else:
            _mem_cache[signature] = program
        _mem_cache.move_to_end(signature)
        while len(_mem_cache) > _MEM_CACHE_MAX:
            _mem_cache.popitem(last=False)
    return program


# ---------------------------------------------------------------------------
# metrics / status / alarm
# ---------------------------------------------------------------------------

_metrics_rec = None
_metrics_lock = threading.Lock()
_alarmed_programs: set = set()


def _metrics():
    global _metrics_rec
    if _metrics_rec is None:
        with _metrics_lock:
            if _metrics_rec is None:
                from ..monitor.metrics import MetricsRecord
                _metrics_rec = MetricsRecord(
                    category="component",
                    labels={"component": "loongresident"})
    return _metrics_rec


def _count(name: str, delta: int = 1) -> None:
    try:
        _metrics().counter(name).add(delta)
    except Exception:  # noqa: BLE001 — stats must never break dispatch
        pass


def _note_demotion(program: FusedProgramKernel, reason: str) -> None:
    """A chunk fell off the fused program to the per-stage path: counted
    always, alarmed once per program — silent demotion would hide exactly
    the round-trip regression this layer exists to remove."""
    program.demotions += 1
    _count("fused_demotions_total")
    with _metrics_lock:
        if program.signature in _alarmed_programs:
            return
        _alarmed_programs.add(program.signature)
    try:
        from ..monitor.alarms import AlarmManager, AlarmType
        AlarmManager.instance().send_alarm(
            AlarmType.FUSED_DEMOTED,
            f"fused pipeline program {program.signature} demoted a chunk "
            f"to per-stage dispatch ({reason}); stages="
            f"{[s.label for s in program.specs]}")
    except Exception:  # noqa: BLE001
        pass


#: why a row of a ``json_fields`` stage was handed to the host's emitter
JSON_HOST_REASONS = ("escape", "shape", "not_object", "overlong")


def note_json_rows(rows: int, host_rows: Optional[dict] = None,
                   signatures_decoded: int = 0) -> None:
    """One group through a ``json_fields`` stage: the rows it held, those
    the host's emitter had to take by reason (`JSON_HOST_REASONS`), and the
    key signatures decoded into names for it."""
    _count("json_rows_total", rows)
    for reason, n in (host_rows or {}).items():
        if n:
            _count(f"json_host_rows_{reason}_total", int(n))
    if signatures_decoded:
        _count("json_signatures_decoded_total", signatures_decoded)


def stage_fusion_status() -> dict:
    """The /debug/status ``stage_fusion`` section: per-program
    dispatch/demotion rows plus the cache counters, and under ``json`` the
    ``json_fields`` stage's row accounting (absent until such a stage has
    seen a group)."""
    with _mem_cache_lock:
        programs = [p.status() for p in _mem_cache.values()]
    doc = {"enabled": fusion_enabled(), "programs": programs}
    try:
        rec = _metrics()
        for name in ("fused_program_cache_hit_total",
                     "fused_program_cache_miss_total",
                     "fused_demotions_total", "fused_dispatch_total",
                     "fused_lane_respill_total"):
            doc[name] = int(rec.counter(name).value)
        rows = int(rec.counter("json_rows_total").value)
        if rows:
            doc["json"] = {
                "rows_total": rows,
                "host_rows_total": {
                    r: int(rec.counter(f"json_host_rows_{r}_total").value)
                    for r in JSON_HOST_REASONS},
                "signatures_decoded_total": int(rec.counter(
                    "json_signatures_decoded_total").value)}
    except Exception:  # noqa: BLE001
        pass
    return doc


def reset_for_testing() -> None:
    """Clear the in-process program cache and one-shot alarm state (tests
    must not inherit another test's dispatch counters or cache hits).
    Metrics records persist — process-lifetime instruments."""
    global _cache_dir
    with _mem_cache_lock:
        _mem_cache.clear()
    with _metrics_lock:
        _alarmed_programs.clear()
    _cache_dir = None


# ---------------------------------------------------------------------------
# the dispatch handle
# ---------------------------------------------------------------------------


def _free_resident(chunk) -> None:
    """A chunk settled (materialised, demoted or abandoned): its
    inter-stage columns no longer live on the device."""
    mem_note_free("resident_columns", chunk.nbytes)


class FusedBatchResult:
    """Assembled per-stage outputs in original row order.

    ``stages[i]`` for stage kind: extract → (ok bool [n], cap_off i32
    [n, C] ARENA-ABSOLUTE, cap_len i32 [n, C]); json_fields → the same
    three, then (status i32 [n], members i32 [n], signature i32 [n, 2]);
    scan → (tags uint32 [n],); label → (label int32 [n],);
    keep → (keep bool [n],); struct_index → (in_string, structural,
    escaped, quote) bool [n, Lmax]."""

    __slots__ = ("stages", "n")

    def __init__(self, stages: List[Tuple[np.ndarray, ...]], n: int):
        self.stages = stages
        self.n = n


class FusedDispatch:
    """One group's fused parse in flight (the PendingParse of the fused
    plane).  ``dispatch()`` submits the ONE fused program per chunk through
    a `DeviceStream` window (ops/device_stream.py), which owns the ring
    discipline — ≤ depth chunks in flight under the DevicePlane budget,
    in-order materialisation, every release; ``result()`` drains it and
    assembles per-stage outputs.  This class's own: the callable a chunk
    rides, the recovery of a faulted chunk — an injected
    ``device_plane.fused_dispatch`` (or h2d/submit) fault, a chip-lane
    fault, or a real kernel failure costs that ONE chunk a demotion to the
    per-stage dispatch path, never events, never ring order — the assembly,
    and the fused plane's accounting."""

    __slots__ = ("program", "arena", "offsets", "lengths", "_window",
                 "_stage_bufs", "_struct_parts", "_result", "_n",
                 "_idle_ms0", "_plane")

    def __init__(self, program: FusedProgramKernel, arena: np.ndarray,
                 offsets: np.ndarray, lengths: np.ndarray,
                 depth: Optional[int] = None):
        self.program = program
        self.arena = arena
        self.offsets = np.asarray(offsets, dtype=np.int64)
        self.lengths = np.asarray(lengths, dtype=np.int32)
        self._n = len(self.offsets)
        self._stage_bufs = self._alloc_stage_bufs()
        self._struct_parts: Dict[int, List] = {}
        self._result: Optional[FusedBatchResult] = None
        from .device_plane import DevicePlane
        self._plane = DevicePlane.instance()
        # fused programs key their tuner floors per program: a sparse
        # fused pipeline must not shrink the staged plane's geometry
        self._window = self._plane.open_stream(
            depth, program="fused", lane=chip_lanes.current_lane(),
            tuner_key=f"fused:{program.signature[:8]}",
            advance_point=FP_FUSED_DISPATCH, recover=self._recover,
            deliver=self._deliver, settled=_free_resident)
        self._idle_ms0 = \
            self._plane.utilization()["idle_while_backlogged_ms"]

    # -- assembly buffers ---------------------------------------------------

    def _alloc_stage_bufs(self) -> List:
        n = self._n
        bufs: List = []
        for spec in self.program.specs:
            if spec.kind in SPAN_STAGES:
                C = max(spec.payload.num_caps, 1)
                spans = (np.zeros(n, dtype=bool),
                         np.zeros((n, C), dtype=np.int32),
                         np.full((n, C), -1, dtype=np.int32))
                if spec.kind == "json_fields":
                    spans += (np.zeros(n, dtype=np.int32),
                              np.zeros(n, dtype=np.int32),
                              np.zeros((n, 2), dtype=np.int32))
                bufs.append(spans)
            elif spec.kind == "scan":
                bufs.append((np.zeros(n, dtype=np.uint32),))
            elif spec.kind == "keep":
                bufs.append((np.zeros(n, dtype=bool),))
            elif spec.kind == "label":
                bufs.append((np.full(n, -1, dtype=np.int32),))
            else:  # struct_index: ragged per-chunk widths, assembled late
                bufs.append(None)
        return bufs

    # -- dispatch -----------------------------------------------------------

    @property
    def done(self) -> bool:
        return self._result is not None

    def dispatch(self) -> "FusedDispatch":
        program = self.program
        window = self._window
        lane = window.lane
        device_idx = np.arange(self._n)
        try:
            for start in range(0, self._n, MAX_BATCH):
                chunk = device_idx[start:start + MAX_BATCH]
                if not window.admit(len(chunk)):
                    # lane OPEN (or the half-open probe is in flight): the
                    # chip is sick — this chunk demotes to the per-stage
                    # path on the base kernels until the probe re-closes
                    # it.  Events still flow, counted as lane respill.
                    program.lane_respills += 1
                    _count("fused_lane_respill_total")
                    self._staged_into(chunk)
                    continue
                override = program._kernel_override
                if override is not None:
                    def call(r, l, _o=override, _p=program):
                        _p.dispatch_count += 1
                        return _o(r, l)
                elif lane is None:
                    # the window takes the program's packed entry
                    call = program
                else:
                    call = lane_gated(lane, program.for_lane(lane))
                c = window.submit_rows(call, self.arena, self.offsets[chunk],
                                       self.lengths[chunk], tag=chunk)
                program.note_geometry(c.slot.B, c.slot.L)
                _count("fused_dispatch_total")
                # loongxprof device-memory ledger: while this chunk is in
                # flight its inter-stage columns live device-side (that
                # residency is the whole point of fusion) — accounted at
                # the input-bytes proxy the plane budget already uses,
                # credited back when the chunk settles (_free_resident)
                mem_note_alloc("resident_columns", c.nbytes)
        except BaseException:
            # a failed pack/submit must not strand the budget, ring slots
            # or lane accounting held by already-submitted chunks
            window.abandon()
            raise
        return self

    # -- materialisation ----------------------------------------------------

    def _recover(self, c, exc) -> List[np.ndarray]:
        """A chunk whose materialisation raised (the window's callback):
        demote this ONE chunk to the per-stage path — its slot still holds
        the packed rows.  A failure there too propagates (that path is the
        proven one)."""
        program = self.program
        if isinstance(exc, ChipLaneFault):
            # injected single-chip fault: the window feeds the lane
            # breaker; the other chips' lanes never notice
            program.lane_respills += 1
            _count("fused_lane_respill_total")
        elif isinstance(exc, chaos.ChaosFault):
            # injected fused-dispatch (or h2d/submit) fault
            _note_demotion(program, "chaos fault at materialise")
        else:
            # real kernel failure (Mosaic/mesh/runtime): cost must be
            # dispatch count, never liveness
            _note_demotion(program, f"kernel failure: {exc!r}")
        return self._staged_flat(c.batch)

    def _deliver(self, c, flat) -> None:
        self._assemble(c.tag, c.batch, flat)
        self.program.roundtrip_ms_total += \
            (time.perf_counter() - c.t_advance) * 1e3

    def _staged_flat(self, batch) -> List[np.ndarray]:
        """Per-stage run of a packed chunk, flattened like the program's
        outputs."""
        outs = self.program.staged_run(batch.rows, batch.lengths)
        return [a for tup in outs for a in tup]

    def _staged_into(self, chunk: np.ndarray) -> None:
        """Pre-dispatch demotion (lane OPEN): pack into a ring slot and
        run the per-stage path synchronously."""
        slot, batch = self._window.pack(self.arena, self.offsets[chunk],
                                        self.lengths[chunk])
        try:
            self._assemble(chunk, batch, self._staged_flat(batch))
        finally:
            slot.release()

    def _assemble(self, chunk: np.ndarray, batch, flat) -> None:
        n_real = batch.n_real
        flat = [np.asarray(a) for a in flat]
        for si, spec in enumerate(self.program.specs):
            start, width = self.program.layout[si]
            outs = flat[start:start + width]
            if spec.kind in SPAN_STAGES:
                ok_b, off_b, len_b = self._stage_bufs[si][:3]
                ok_b[chunk] = outs[0][:n_real]
                # row-relative -> arena-absolute via the pack origins
                off_b[chunk] = (outs[1][:n_real]
                                + batch.origins[:n_real, None])
                len_b[chunk] = outs[2][:n_real]
                for buf, out in zip(self._stage_bufs[si][3:], outs[3:]):
                    buf[chunk] = out[:n_real]
            elif spec.kind == "scan":
                self._stage_bufs[si][0][chunk] = \
                    outs[0][:n_real].astype(np.uint32)
            elif spec.kind == "keep":
                self._stage_bufs[si][0][chunk] = \
                    np.asarray(outs[0][:n_real], dtype=bool)
            elif spec.kind == "label":
                self._stage_bufs[si][0][chunk] = outs[0][:n_real]
            else:  # struct_index: keep packed words per chunk, unpack late
                self._struct_parts.setdefault(si, []).append(
                    (chunk, [o[:n_real] for o in outs], batch.rows.shape[1]))

    def result(self) -> FusedBatchResult:
        if self._result is not None:
            return self._result
        self._window.drain()
        stages: List[Tuple[np.ndarray, ...]] = []
        for si, spec in enumerate(self.program.specs):
            if spec.kind == "struct_index":
                stages.append(self._finish_struct(si))
            else:
                stages.append(self._stage_bufs[si])
        idle_now = self._plane.utilization()["idle_while_backlogged_ms"]
        self.program.idle_attr_ms += max(0.0, idle_now - self._idle_ms0)
        self._result = FusedBatchResult(stages, self._n)
        # drop references so the arena frees promptly; the window holds
        # this object's bound methods, so letting go of it also undoes the
        # cycle (no wait for the collector)
        self.arena = self._window = None
        return self._result

    def _finish_struct(self, si: int) -> Tuple[np.ndarray, ...]:
        from .kernels.struct_index import unpack16
        parts = self._struct_parts.get(si, [])
        Lmax = max((L for _c, _m, L in parts), default=0)
        out = tuple(np.zeros((self._n, Lmax), dtype=bool) for _ in range(4))
        for chunk, masks, L in parts:
            for mi in range(4):
                out[mi][chunk, :L] = unpack16(masks[mi], L)
        return out

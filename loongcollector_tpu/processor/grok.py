"""processor_grok — grok pattern field extraction.

Reference: plugins/processor/grok/ (Go) — pattern library + %{NAME:field}
expansion; ``Match`` is an ORDERED list: the patterns are tried from the top
and the first that fully matches a row gives the row its fields.  Expansion
feeds the tiered RegexEngine, one engine a member.

Two legs, the protocol of processor_parse_regex_tpu and the multiline split,
and two paths through them.

**The list program.**  A list whose members are all on the SEGMENT tier is
one device program a group (``ops/kernels/match_list.py``): the group's rows
are packed once into one ring slot and submitted once through the one
dispatch window (``PendingMatchList``), every member's extract runs over the
same rows inside the one module, and the first-match choice is made there —
a row's member is the lowest member whose full-match flag is set.
``process_complete`` installs the one pair of span matrices that comes back.
No host classify, no per-member subsets, no second opinion by ``re``.  It is
chosen from what the code can observe: at ``init`` that every member has a
SEGMENT-tier device kernel, per group that the routing rule every regex
dispatch uses (``routes_to_host``), applied to the whole group's byte sum,
sends the rows to the device.  Rows over the largest length bucket meet
``re`` member by member, beside the dispatch.  A chunk the program cannot
serve (a sick chip lane, a real failure) sends its group down the
per-member path, and a real failure pins the processor there, counted in
``device.routing.kernel_fallbacks_total``.

**The per-member path** serves every other list and group, and a fused
chain's scan stage.  ``process_dispatch`` classifies every row once — one
scan of the list's fused full-match automaton on the host's byte-table
scanner: a row's member is the lowest set bit of its tag, so the order is
settled before anything is extracted — and starts each member's extract over
its own rows with
``parse_batch_async``, in ``Match`` order.  A SEGMENT-tier subset above the
routing crossover rides a ``PendingParse`` through the one dispatch window
and stays in flight; a subset under it runs on the native walker there and
then, while the device works.  ``process_complete`` materialises the handles
and writes the members' spans into one matrix of the union of their keys, in
``Match`` order: a field of a member absent from a row stays absent (length
-1), a row no member matches keeps its source under ``rawLog``.
``process`` is the two legs in a row.

A member the automaton could not hold (demoted: its mask is None), and every
member of a list that does not fuse at all, probes what is still unmatched
when its turn comes; what is left for the members behind it is what it did
not take, so unless it is the last of the list it is waited for inside the
dispatch leg.  A ``Match`` of one pattern is a list of one with no classify.

Python's ``re`` meets a row only where nothing else can: a CPU-tier member,
a row over the largest length bucket, a row whose engine and automaton
disagree.  Every such loop runs under a ``grok.re_rows`` span and is counted
(``re_rows_total``), so per-row work under the interpreter lock is never
invisible.

The classify runs where ``FusedSetExec.classify`` puts it, on the host's
scanner (PERF.md section 7, ROADMAP A3).  A ``device_ok`` list still joins a
fused pipeline program as its scan stage (``fused_stage_spec``), which rides
that program's window.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..models import PipelineEventGroup
from ..ops import chip_lanes
from ..ops.device_batch import LENGTH_BUCKETS
from ..ops.device_plane import budget_relief_first
from ..ops.regex.engine import (PendingMatchList, RegexEngine, get_engine,
                                pallas_by_default, routes_to_host)
from ..ops.regex.grok import GrokError, expand
from ..pipeline.plugin.interface import PluginContext, Processor
from .common import RAW_LOG_KEY, extract_source, stage_span

# /debug/status ``grok``: per pipeline, cumulative for the process
COUNTERS = ("rows_total", "device_rows_total", "list_program_rows_total",
            "walker_rows_total", "re_rows_total", "unmatched_rows_total",
            "dispatches_total")
_stats_lock = threading.Lock()
_stats: Dict[str, dict] = {}
# the list programs of the process, by (the members' patterns, Pallas or
# XLA): a pipeline reload reuses the compiled program, as get_engine does
_list_kernels_lock = threading.Lock()
_list_kernels: Dict[tuple, Any] = {}


def _note(pipeline: str, member_rows=(), **deltas: int) -> None:
    with _stats_lock:
        row = _stats.get(pipeline)
        if row is None:
            row = _stats[pipeline] = dict.fromkeys(COUNTERS, 0)
            row["member_rows_total"] = []
        for k, v in deltas.items():
            row[k] += v
        taken = row["member_rows_total"]
        taken.extend([0] * (len(member_rows) - len(taken)))
        for i, v in enumerate(member_rows):
            taken[i] += v


def status() -> Dict[str, dict]:
    """The ``grok`` section of /debug/status: by pipeline, rows through the
    stage (``rows_total``), where the row's member extracted it — the device
    through the dispatch window, the native walker under the routing
    crossover, Python ``re`` (``device_rows_total`` + ``walker_rows_total``
    + ``re_rows_total`` + ``unmatched_rows_total`` = ``rows_total``) —, rows
    no member matched (``rawLog``), rows that rode the one-program dispatch
    of the whole list (``list_program_rows_total``: no term of that sum —
    those a member took are ``device_rows_total``'s, the others
    ``unmatched_rows_total``'s), rows each member of ``Match`` took by its
    position (``member_rows_total``), and groups through the dispatch leg
    (``dispatches_total``)."""
    with _stats_lock:
        return {k: dict(v, member_rows_total=list(v["member_rows_total"]))
                for k, v in _stats.items()}


def reset_for_testing() -> None:
    with _stats_lock:
        _stats.clear()
    with _list_kernels_lock:
        _list_kernels.clear()


def _run(positions: List[int]):
    """``positions`` as a slice where they are a run (a member's named
    groups, and the columns it was first to name), else as an index array.
    A slice moves whole row pieces at once: on the chip's host it takes
    0.49 s per GB out of ``grok.apply`` and gives the grok cell 1.7-4.0 % in
    three same-seed pairs (PERF.md section 6, PR 34, call F)."""
    if positions and positions == list(range(positions[0],
                                             positions[-1] + 1)):
        return slice(positions[0], positions[-1] + 1)
    return np.array(positions, dtype=np.intp)


class _Part:
    """One member's extract over its rows of one group."""

    __slots__ = ("member", "rows", "pending", "on_walker")

    def __init__(self, member: int, rows: np.ndarray, pending):
        self.member = member
        self.rows = rows
        self.pending = pending
        # finished at dispatch: routing kept the subset on the host walker
        self.on_walker = pending.done


class ProcessorGrok(Processor):
    name = "processor_grok"
    supports_columnar = True
    supports_async_dispatch = True

    def __init__(self) -> None:
        super().__init__()
        self.source_key = b"content"
        self.keep_source_on_fail = True
        self.renamed_source_key = RAW_LOG_KEY
        self._pipeline = ""
        self._engines: List[Tuple[RegexEngine, List[str]]] = []
        self._fused_set = None
        #: the union of the members' keys in order of first appearance, and
        #: per member (its named captures, their columns in the union)
        self._keys: List[str] = []
        self._columns: List[Tuple[np.ndarray, np.ndarray]] = []
        #: the same map as plain lists, for the list program's trace
        self._placement: List[Tuple[List[int], List[int]]] = []
        #: the whole list as one device program: possible at all (every
        #: member on the SEGMENT tier; False for good after a real failure
        #: of the program), and the kernel object, built at first use
        self._list_ok = False
        self._list_kernel = None

    def init(self, config: Dict[str, Any], context: PluginContext) -> bool:
        super().init(config, context)
        match = config.get("Match", [])
        if isinstance(match, str):
            match = [match]
        if not match:
            return False
        custom = config.get("CustomPatterns", {}) or {}
        self.source_key = config.get("SourceKey", "content").encode()
        self.keep_source_on_fail = bool(
            config.get("KeepingSourceWhenParseFail", True))
        import re as _re
        for pattern in match:
            try:
                regex = expand(pattern, custom)
                engine = get_engine(regex)
            except (GrokError, _re.error):
                return False
            # only NAMED groups become fields (grok semantics)
            keys = [engine.group_names.get(i, "") for i in range(engine.num_caps)]
            self._engines.append((engine, keys))
        column_of: Dict[str, int] = {}
        for _engine, keys in self._engines:
            named = [g for g, key in enumerate(keys) if key]
            columns = [column_of.setdefault(keys[g], len(column_of))
                       for g in named]
            self._placement.append((named, columns))
            self._columns.append((_run(named), _run(columns)))
        self._keys = list(column_of)
        self._list_ok = len(self._engines) > 1 and bool(self._keys) and all(
            e._segment_kernel is not None for e, _ in self._engines)
        # loongfuse: with several Match patterns, one fused scan classifies
        # them all — each event runs ONLY its first-matching pattern's
        # extract program instead of trying every engine in order.  A lone
        # pattern already fuses inside its own engine.
        self._fused_set = None
        if len(self._engines) > 1:
            from ..ops.regex.fuse import try_build_set
            self._fused_set = try_build_set(
                [e.pattern for e, _ in self._engines],
                names=[f"match{i}" for i in range(len(self._engines))])
        self._pipeline = getattr(context, "pipeline_name", "") or ""
        return True

    def process(self, group: PipelineEventGroup) -> None:
        self.process_complete(group, self.process_dispatch(group))

    def process_dispatch(self, group: PipelineEventGroup):
        """Classify the group's rows and start every member's extract.  The
        token holds the handles while a device subset is in flight; None
        when the whole stage ran here."""
        src = extract_source(group, self.source_key)
        if src is None or not len(src.offsets):
            return None
        if not src.columnar:
            with stage_span("grok.re_rows"):
                self._process_rows(group)
            return None
        route = self._list_route(src) if self._list_ok else None
        if route is not None:
            return self._dispatch_list(group, src, *route)
        return self._dispatch_classified(group, src)

    def _dispatch_classified(self, group, src):
        """The per-member path's dispatch leg: the host classify, then
        every member's extract over its own rows."""
        masks: List[Optional[np.ndarray]] = [None] * len(self._engines)
        if self._fused_set is not None:
            with stage_span("grok.classify"):
                masks = self._fused_set.member_masks(
                    self._fused_set.classify(src.arena, src.offsets,
                                             src.lengths))
        return self._dispatch_members(group, src, masks)

    # -- the list program -------------------------------------------------

    def _list_program(self, lane):
        """The kernel object of the whole list (one a process and list, as
        engines are), or None where the members' own dispatches would not
        be single-device ones (an unbound dispatch on a multi-chip host
        shards over the mesh)."""
        engines = [e for e, _ in self._engines]
        if lane is None and engines[0]._maybe_sharded() is not None:
            return None
        if self._list_kernel is None:
            key = tuple(e.pattern for e in engines), pallas_by_default()
            with _list_kernels_lock:
                kernel = _list_kernels.get(key)
                if kernel is None:
                    from ..ops.kernels.match_list import MatchListKernel
                    kernel = _list_kernels[key] = MatchListKernel(
                        [e._segment_kernel.program for e in engines],
                        self._placement, len(self._keys), key[1])
            self._list_kernel = kernel
        return self._list_kernel

    def _list_route(self, src):
        """``(kernel, rows)`` where the list program serves this group —
        the rows it takes are those present and within the largest length
        bucket, None for "every row" (the common case: no mask, no index
        array) — or None where the per-member path does: the routing rule
        keeps the rows' byte sum on the host, or the dispatch would not be
        a single-device one."""
        lengths = src.lengths
        idx = None
        if int(lengths.max()) > LENGTH_BUCKETS[-1] \
                or not (src.from_content or int(lengths.min()) >= 0):
            idx = np.flatnonzero(src.present
                                 & (lengths <= LENGTH_BUCKETS[-1]))
            lengths = lengths[idx]
        if routes_to_host(lengths, lambda: True):
            return None
        kernel = self._list_program(chip_lanes.current_lane())
        return None if kernel is None else (kernel, idx)

    def _dispatch_list(self, group, src, kernel, idx):
        """One pack, one submit for the whole group."""
        pending = PendingMatchList(kernel, src.arena, src.offsets,
                                   src.lengths)
        if idx is not None and not len(idx):
            self._complete_list(group, src, pending)
            return None
        with stage_span("grok.members.dispatch"):
            pending.dispatch(idx)
        return src, pending

    def _complete_list(self, group, src, pending: PendingMatchList) -> None:
        try:
            res = pending.result()
        except BaseException:
            pending.abandon()
            raise
        if pending.failed:
            self._list_ok = False
        if pending.host_rows:
            # a chunk the program could not serve: the whole group takes
            # the per-member path, which has its own host tiers
            self.process_complete(group,
                                  self._dispatch_classified(group, src))
            return
        with stage_span("grok.apply"):
            self._apply_list(group, src, res.ok, res.cap_off, res.cap_len,
                             pending.rode)

    def _apply_list(self, group, src, member, off_mat, len_mat,
                    n_rode: int) -> None:
        """The list program's one pair of matrices into the group; rows
        over the largest bucket first meet ``re`` member by member.  Few
        numpy calls where every row rode (``n_rode`` is the group's rows):
        each costs the worker a hand-over of the interpreter lock."""
        cols = group.columns
        n = len(member)
        n_present, n_re = n, 0
        if n_rode < n:
            n_present = int(np.count_nonzero(src.present))
            over = np.flatnonzero(src.present
                                  & (src.lengths > LENGTH_BUCKETS[-1]))
            if len(over):
                def install(j, rows, cap_off, cap_len):
                    self._place(off_mat, len_mat, j, rows, cap_off, cap_len)
                    member[rows] = j
                n_re = self._decide_by_re(src, over, 0, install)
        matched = member >= 0
        cols.set_fields_matrix(self._keys, off_mat, len_mat)
        # 0: no member; 1 + i: member i of ``Match``
        counts = np.bincount(member + 1, minlength=len(self._engines) + 1)
        n_matched = n - int(counts[0])
        if self.keep_source_on_fail and n_matched < n_present:
            fail = ~matched if n_present == n else ~matched & src.present
            cols.set_field(self.renamed_source_key,
                           src.offsets.astype(np.int32),
                           np.where(fail, src.lengths, np.int32(-1)))
        cols.parse_ok = matched
        if src.from_content:
            cols.content_consumed = True
        _note(self._pipeline, counts[1:].tolist(), rows_total=n,
              dispatches_total=1, device_rows_total=n_matched - n_re,
              list_program_rows_total=n_rode, re_rows_total=n_re,
              unmatched_rows_total=n - n_matched)

    # -- the per-member path ----------------------------------------------

    def _dispatch_members(self, group, src, masks):
        parts: List[_Part] = []
        try:
            with stage_span("grok.members.dispatch"), \
                    budget_relief_first(lambda: self._relieve(parts)):
                self._place_members(src, masks, parts)
        except BaseException:
            self._abandon(parts)
            raise
        _note(self._pipeline, dispatches_total=1)
        token = src, parts
        if all(part.pending.done for part in parts):
            self.process_complete(group, token)
            return None
        return token

    @staticmethod
    def _abandon(parts: List[_Part]) -> None:
        """A leg failed with members' chunks in flight: nobody will ask for
        them, so slots, budget and lane bytes go back now."""
        for part in parts:
            part.pending.abandon()

    def _relieve(self, parts: List[_Part]) -> bool:
        """Budget relief while a later member waits to dispatch: this
        group's oldest handle still in flight is materialised (its result
        is kept for the complete leg), which gives its bytes back."""
        for part in parts:
            if not part.pending.done:
                self._result(part)
                return True
        return False

    def _place_members(self, src, masks, parts: List[_Part]) -> None:
        remaining = src.present.copy()
        last = len(self._engines) - 1
        for i, (engine, _keys) in enumerate(self._engines):
            if masks[i] is not None:
                # the tags settle the order: the row is this member's
                idx = np.flatnonzero(remaining & masks[i])
                remaining[idx] = False
            else:
                idx = np.flatnonzero(remaining)
            if not len(idx):
                continue
            part = _Part(i, idx, engine.parse_batch_async(
                src.arena, src.offsets[idx], src.lengths[idx]))
            parts.append(part)
            if masks[i] is None and i < last:
                # what is left for the members behind a probing member is
                # what it did not take: it is waited for here
                remaining[idx[self._result(part).ok]] = False

    @staticmethod
    def _result(part: _Part):
        """The part's spans; a handle with rows for Python ``re`` (a CPU-
        tier member, rows over the largest bucket) runs that loop inside
        ``result()``, under the span that says so."""
        pending = part.pending
        if pending.done or not len(pending.cpu_idx):
            return pending.result()
        with stage_span("grok.re_rows"):
            return pending.result()

    def process_complete(self, group: PipelineEventGroup, token) -> None:
        if token is None:
            return
        src, parts = token
        if isinstance(parts, PendingMatchList):
            self._complete_list(group, src, parts)
            return
        try:
            results = [self._result(part) for part in parts]
        except BaseException:
            self._abandon(parts)
            raise
        with stage_span("grok.apply"):
            self._apply(group, src, parts, results)

    def _place(self, off_mat, len_mat, member: int, rows, cap_off,
               cap_len) -> None:
        """``member``'s spans of ``rows`` into the union's columns (the
        column map both paths share)."""
        caps, columns = self._columns[member]
        # a run of columns takes whole row pieces; scattered ones (a key
        # shared with a member further up) go cell by cell
        at = rows if isinstance(columns, slice) else rows[:, None]
        off_mat[at, columns] = cap_off[:, caps]
        len_mat[at, columns] = cap_len[:, caps]

    def _apply(self, group, src, parts: List[_Part], results) -> None:
        """The members' spans into one matrix of the union keys, in Match
        order; rawLog for the rows no member took."""
        n = len(src.offsets)
        cols = group.columns
        off_mat = np.zeros((n, len(self._keys)), dtype=np.int32)
        len_mat = np.full((n, len(self._keys)), -1, dtype=np.int32)
        matched = np.zeros(n, dtype=bool)
        taken = [0] * len(self._engines)
        tiers = {"device": 0, "walker": 0, "re": 0}

        def install(member, rows, cap_off, cap_len):
            self._place(off_mat, len_mat, member, rows, cap_off, cap_len)
            matched[rows] = True
            taken[member] += len(rows)

        for part, res in zip(parts, results):
            ok = res.ok
            n_ok = int(ok.sum())
            if n_ok == len(ok):
                install(part.member, part.rows, res.cap_off, res.cap_len)
            else:
                install(part.member, part.rows[ok], res.cap_off[ok],
                        res.cap_len[ok])
            on_re = 0
            if part.on_walker:
                tiers["walker"] += n_ok
            else:
                cpu_idx = part.pending.cpu_idx
                on_re = int(ok[cpu_idx].sum()) if len(cpu_idx) else 0
                tiers["re"] += on_re
                tiers["device"] += n_ok - on_re
            left = part.rows[~ok]
            member_mask_known = self._fused_set is not None \
                and part.member in self._fused_set.bit_of
            if len(left) and member_mask_known:
                # the classify gave these rows to this member and its
                # engine did not take them: the automaton and an engine
                # disagree, and ``re`` decides them from this member on
                tiers["re"] += self._decide_by_re(src, left, part.member,
                                                  install)
        if self._keys:
            cols.set_fields_matrix(self._keys, off_mat, len_mat)
        fail = ~matched & src.present
        n_fail = int(fail.sum())
        if self.keep_source_on_fail and n_fail:
            cols.set_field(self.renamed_source_key,
                           src.offsets.astype(np.int32),
                           np.where(fail, src.lengths, -1).astype(np.int32))
        cols.parse_ok = matched
        if src.from_content:
            cols.content_consumed = True
        _note(self._pipeline, taken, rows_total=n,
              device_rows_total=tiers["device"],
              walker_rows_total=tiers["walker"], re_rows_total=tiers["re"],
              unmatched_rows_total=n - int(matched.sum()))

    def _decide_by_re(self, src, rows, first: int, install) -> int:
        """``re`` decides ``rows``, member by member from ``first`` on;
        how many it matched."""
        decided = 0
        with stage_span("grok.re_rows"):
            for j in range(first, len(self._engines)):
                if not len(rows):
                    break
                engine = self._engines[j][0]
                C = max(engine.num_caps, 1)
                ok = np.zeros(len(rows), dtype=bool)
                off = np.zeros((len(rows), C), dtype=np.int32)
                ln = np.full((len(rows), C), -1, dtype=np.int32)
                engine._cpu_fallback_rows(
                    src.arena, src.offsets[rows], src.lengths[rows],
                    range(len(rows)), ok, off, ln)
                install(j, rows[ok], off[ok], ln[ok])
                decided += int(ok.sum())
                rows = rows[~ok]
        return decided

    def fused_stage_spec(self, ctx):
        """loongresident: the multi-pattern classify scan joins a fused
        pipeline program as a ``scan`` stage (one tag bitmask per row);
        extraction still runs per matching subset afterwards — the scan
        is the stage that used to cost one dispatch per pattern.  Grok's
        dynamic fields never register as capture bindings (they are
        extracted host-side), so later members cannot bind them — by
        design, not by accident."""
        fs = self._fused_set
        if fs is None or not fs.fdfa.device_ok:
            return None
        if not ctx.bind_source(self.source_key):
            return None
        from ..ops import fused_pipeline as fp
        from ..pipeline.fused_chain import FusedMemberStage
        spec = fp.StageSpec("scan", fs.fdfa,
                            ["scan"] + list(fs.fdfa.patterns),
                            staged=fs._device_kernel(),
                            label="grok-classify")
        ctx.note_consumed(self.source_key)
        return FusedMemberStage(spec, self._fused_apply)

    def _fused_apply(self, group, src, out, rowmap):
        from .common import subset_source
        tags = np.asarray(out[0]).astype(np.uint32)[rowmap]
        masks = self._fused_set.member_masks(tags)
        self.process_complete(group, self._dispatch_members(
            group, subset_source(src, rowmap), masks))
        return rowmap

    def _process_rows(self, group: PipelineEventGroup) -> None:
        # row path — shared reference keep/discard ordering; every row of
        # it is ``re``'s and is counted so
        from .common import finish_row_keep
        sb = group.source_buffer
        renamed = self.renamed_source_key.encode()
        rows = 0
        taken = [0] * len(self._engines)
        for ev in group.events:
            if not hasattr(ev, "get_content"):
                continue
            raw = ev.get_content(self.source_key)
            if raw is None:
                continue
            rows += 1
            data = raw.to_bytes()
            hit = False
            overwritten = False
            for j, (engine, keys) in enumerate(self._engines):
                m = engine._re.fullmatch(data)
                if m is None:
                    continue
                hit = True
                taken[j] += 1
                for g, key in enumerate(keys):
                    if key and m.group(g + 1) is not None:
                        kb = key.encode()
                        ev.set_content(kb, sb.copy_string(m.group(g + 1)))
                        if kb == self.source_key:
                            overwritten = True
                break
            finish_row_keep(ev, raw, hit, self.source_key, overwritten,
                            self.keep_source_on_fail, False, renamed)
        _note(self._pipeline, taken, rows_total=rows,
              re_rows_total=sum(taken),
              unmatched_rows_total=rows - sum(taken))

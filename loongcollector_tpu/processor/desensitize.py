"""processor_desensitize_native — mask credentials inside a field.

Reference: core/plugin/processor/ProcessorDesensitizeNative.cpp and its
documentation (processor-desensitize-native.md).  The pattern is ``(`` +
``ContentPatternBeforeReplacedString`` + ``)`` + ``ReplacedContentPattern``
over the field ``SourceKey``.  ``Method: const`` replaces each match by
``\\1`` + ``ReplacingString`` — RE2's GlobalReplace where ``ReplacingAll``
(the default), Replace (the first match alone) where not: leftmost-first,
non-overlapping, greedy.  ``Method: md5`` puts the MD5 hex digest of the
matched content in its place.  A record without the field is left as it is.
``Regex`` is this repo's alias: one pattern whose group 1 is kept and whose
group 2 is masked.

Two tiers, chosen from what the code observes:

* **fused** (`fused_stage_spec`): behind a stage that publishes
  ``SourceKey`` as a span column (``processor_parse_regex_tpu``'s capture),
  a ``const`` pair the device takes (``ops/regex/fuse.py``
  `try_build_mask`: a prefix of fixed-length alternatives, content one byte
  class under a greedy count) is ONE ``mask`` stage of that run's device
  program: the find-all walk over the span hands back up to `SLOTS` content
  spans a row and a count (``ops/kernels/dfa_scan.py``
  `build_span_mask_fn`).  `_fused_apply` writes every changed value of the
  group into one arena block by one native call (``lct_mask_spans``) and
  installs the field with one ``set_field``; an unchanged row keeps its
  span.  A row the stage does not decide — more than `SLOTS` matches, a byte
  >= 0x80, a demoted chunk, a span the device did not produce — goes to
* **the host tier** (`process`, `_host_rows`): ``re`` over each value, one
  pass a group, the changed values into one arena block, one ``set_field``.
  Also every other position in a pipeline, a pair the device does not take,
  ``md5`` and the ``Regex`` alias.

`/debug/status` ``desensitize`` has, by pipeline, where the rows went; the
spans are ``desensitize.apply`` (the fused run's rebuild and install) and
``desensitize.host``.
"""

from __future__ import annotations

import hashlib
import re
import threading
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..models import PipelineEventGroup
from ..pipeline.plugin.interface import PluginContext, Processor
from .common import stage_span

#: matches a row the ``mask`` stage hands back; a row with more is the host's
SLOTS = 4

#: /debug/status ``desensitize``: per pipeline, cumulative for the process
COUNTERS = ("rows_total", "mask_program_rows_total", "absent_rows_total",
            "masked_rows_total", "matches_total")
#: why a row with the field was masked on the host: the pair has no device
#: form (``tier``; also a span the device did not produce), more matches
#: than the stage's slots, a byte >= 0x80 in the value, a device-ready pair
#: no fused program ran (``no_fusion``), a demoted chunk
HOST_REASONS = ("tier", "over_slots", "non_ascii", "no_fusion", "demoted")
_stats_lock = threading.Lock()
_stats: Dict[str, dict] = {}


def _note(pipeline: str, host: Optional[Dict[str, int]] = None,
          **deltas: int) -> None:
    with _stats_lock:
        row = _stats.get(pipeline)
        if row is None:
            row = _stats[pipeline] = dict.fromkeys(COUNTERS, 0)
            row["host_rows_total"] = dict.fromkeys(HOST_REASONS, 0)
        for k, v in deltas.items():
            row[k] += int(v)
        for k, v in (host or {}).items():
            row["host_rows_total"][k] += int(v)


def status() -> Dict[str, dict]:
    """The ``desensitize`` section of /debug/status: by pipeline, rows
    through the stage (``rows_total``), where a row with the field was
    decided — the fused program's ``mask`` stage
    (``mask_program_rows_total``) or the host (``host_rows_total``, by
    reason: `HOST_REASONS`) —, rows without the field
    (``absent_rows_total``; the three add up to ``rows_total``), rows whose
    value changed (``masked_rows_total``) and matches replaced
    (``matches_total``)."""
    with _stats_lock:
        return {k: dict(v, host_rows_total=dict(v["host_rows_total"]))
                for k, v in _stats.items()}


def reset_for_testing() -> None:
    with _stats_lock:
        _stats.clear()


class ProcessorDesensitize(Processor):
    name = "processor_desensitize_native"
    supports_columnar = True

    def __init__(self) -> None:
        super().__init__()
        self.source_key = b"content"
        self.method = "const"        # const | md5
        self.replacing = b"********"
        self.replace_all = True
        self.regex = None
        #: the ``Regex`` alias: group 1 kept, group 2 masked
        self._alias = False
        #: the pair's device form, where the device tier takes it
        self._plan = None
        self._pipeline = ""

    def init(self, config: Dict[str, Any], context: PluginContext) -> bool:
        super().init(config, context)
        self.source_key = config.get("SourceKey", "content").encode()
        self.method = config.get("Method", "const")
        if self.method not in ("const", "md5"):
            return False
        self.replacing = config.get("ReplacingString", "********").encode()
        self.replace_all = bool(config.get("ReplacingAll", True))
        content = config.get("ReplacedContentPattern", "")
        if content:
            before = config.get("ContentPatternBeforeReplacedString",
                                "").encode()
            try:
                self.regex = re.compile(b"(" + before + b")"
                                        + content.encode())
            except re.error:
                return False
            if self.method == "const":
                from ..ops.regex.fuse import try_build_mask
                self._plan = try_build_mask(before, content.encode(), SLOTS,
                                            self.replace_all)
        else:
            pattern = config.get("Regex", "")
            if not pattern:
                return False
            self.regex = re.compile(pattern.encode())
            self._alias = True
        self._pipeline = getattr(context, "pipeline_name", "") or ""
        return True

    # -- the replacement ----------------------------------------------------

    def _mask(self, m: "re.Match") -> bytes:
        prefix = m.group(1) if m.lastindex and m.lastindex >= 1 else b""
        if self._alias:
            # group 1 is kept as-is; group 2 (the sensitive span) is replaced
            target = m.group(2) if m.lastindex and m.lastindex >= 2 \
                else m.group(0)
        else:
            target = m.string[m.end(1):m.end()]
        if self.method == "md5":
            return prefix + hashlib.md5(target).hexdigest().encode()
        return prefix + self.replacing

    def _sub(self, value: bytes) -> Tuple[bytes, int]:
        """``value`` masked, and the matches replaced."""
        return self.regex.subn(self._mask, value,
                               count=0 if self.replace_all else 1)

    # -- the fused run ------------------------------------------------------

    def fused_stage_spec(self, ctx):
        """loongresident: the pair joins a fused pipeline program as ONE
        ``mask`` stage — the find-all walk over the span column a prior
        member publishes for ``SourceKey``, up to `SLOTS` content spans a
        row out.  Refuses, and keeps the host tier, where the pair has no
        device form or the field is no prior member's capture.  The field's
        bytes change at apply time, so no later member may bind it."""
        if self._plan is None:
            return None
        binding = ctx.resolve(self.source_key)
        if binding is None or binding == "source":
            return None
        from ..ops import fused_pipeline as fp
        from ..pipeline.fused_chain import FusedMemberStage
        spec = fp.StageSpec("mask", self._plan, ["mask"] + self._plan.ident(),
                            staged=self._staged_mask,
                            binding=tuple(binding[1:]),
                            label=f"mask:{self.name}")
        ctx.note_rewritten(self.source_key)
        return FusedMemberStage(spec, self._fused_apply)

    def _staged_mask(self, rows, lengths, starts, spanlens):
        """The ``mask`` stage of a demoted chunk: every row with the field
        is left to the host tier."""
        from ..ops.kernels.dfa_scan import MASK_ABSENT, MASK_DEMOTED
        spanlens = np.asarray(spanlens)
        S = self._plan.slots
        count = np.where(spanlens >= 0, MASK_DEMOTED,
                         MASK_ABSENT).astype(np.int32)
        zeros = np.zeros((len(count), S), dtype=np.int32)
        return count, zeros, zeros.copy()

    def _fused_apply(self, group, src, out, rowmap):
        with stage_span("desensitize.apply"):
            n = len(rowmap)
            spans = group.columns.fields.get(self.source_key.decode("latin-1"))
            if spans is None:
                _note(self._pipeline, rows_total=n, absent_rows_total=n)
                return rowmap
            # no member compacted the group: the stage's own columns
            count, first, last = out if n == len(out[0]) \
                else (a[rowmap] for a in out)
            offs, lens = spans
            present = lens >= 0
            decided = present & (count >= 0) & (count <= self._plan.slots)
            if src.undecided is not None:
                # spans a json_fields producer left to the host's emitter
                decided &= ~src.undecided[rowmap]
            new_offs = np.array(offs, dtype=np.int32)
            new_lens = np.array(lens, dtype=np.int32)
            changing = decided & (count > 0)
            masked = int(np.count_nonzero(changing))
            if masked and not self._rebuild(
                    group, offs, lens,
                    np.where(changing, count, 0).astype(np.int32), first,
                    last, new_offs, new_lens):
                # spans that do not lie inside their values: re decides
                decided &= ~changing
                masked = 0
            matches = int(count[decided].sum())
            rest = np.flatnonzero(present & ~decided)
            host_masked, host_matches = self._host_rows(
                group, offs, lens, rest, new_offs, new_lens)
            if masked or host_masked:
                group.columns.set_field(self.source_key.decode("latin-1"),
                                        new_offs, new_lens)
            n_decided = int(np.count_nonzero(decided))
            _note(self._pipeline, self._host_reasons(count[rest]),
                  rows_total=n, mask_program_rows_total=n_decided,
                  absent_rows_total=n - n_decided - len(rest),
                  masked_rows_total=masked + host_masked,
                  matches_total=matches + host_matches)
        return rowmap

    def _host_reasons(self, count: np.ndarray) -> Dict[str, int]:
        """Why the fused run's rows of these ``count`` readings went to the
        host, by `HOST_REASONS`."""
        from ..ops.kernels.dfa_scan import MASK_DEMOTED, MASK_NON_ASCII
        if not len(count):
            return {}
        over = int(np.count_nonzero(count > self._plan.slots))
        wide = int(np.count_nonzero(count == MASK_NON_ASCII))
        demoted = int(np.count_nonzero(count == MASK_DEMOTED))
        return {"over_slots": over, "non_ascii": wide, "demoted": demoted,
                "tier": len(count) - over - wide - demoted}

    def _rebuild(self, group, offs, lens, count, first, last, new_offs,
                 new_lens) -> bool:
        """Every row whose ``count`` is above 0: its value with the content
        spans ``first[r, :count]`` .. ``last[r, :count]`` replaced, written
        into ONE new arena block, its new span stored into ``new_offs`` /
        ``new_lens``.  One native call where the library is there.  False
        where a row's spans do not lie inside its value."""
        from .. import native
        rows = np.flatnonzero(count)
        c = count[rows]
        S = first.shape[1]
        cut = np.where(np.arange(S)[None, :] < c[:, None],
                       last[rows] - first[rows], 0).sum(axis=1)
        size = int((lens[rows] - cut).sum()) + int(c.sum()) \
            * len(self.replacing)
        if size < 0:
            return False
        sb = group.source_buffer
        base = sb.allocate(size)
        arena = sb.as_array()
        got = native.mask_spans(arena, offs, lens, count, first, last,
                                self.replacing, base, new_offs, new_lens)
        if got is None and native.get_lib() is not None:
            return False
        if got is None:
            got = self._rebuild_rows(arena, base, offs, lens, rows, c, first,
                                     last, new_offs, new_lens)
        return got == size

    def _rebuild_rows(self, arena, base, offs, lens, rows, c, first, last,
                      new_offs, new_lens) -> int:
        """`_rebuild`'s copy loop for a process without the library."""
        at = base
        for r, k_n in zip(rows.tolist(), c.tolist()):
            o, ln = int(offs[r]), int(lens[r])
            cur, parts = o, []
            for k in range(k_n):
                f, l_ = int(first[r, k]), int(last[r, k])
                if not cur <= f <= l_ <= o + ln:
                    return -1
                parts += [arena[cur:f].tobytes(), self.replacing]
                cur = l_
            value = b"".join(parts) + arena[cur:o + ln].tobytes()
            arena[at:at + len(value)] = np.frombuffer(value, np.uint8)
            new_offs[r], new_lens[r] = at, len(value)
            at += len(value)
        return at - base

    # -- the host tier ------------------------------------------------------

    def _host_rows(self, group, offs, lens, rows, new_offs,
                   new_lens) -> Tuple[int, int]:
        """``re`` over the values of ``rows``: the changed ones into one
        arena block, their spans into ``new_offs`` / ``new_lens``.  Returns
        the rows changed and the matches replaced."""
        if not len(rows):
            return 0, 0
        raw = group.source_buffer.as_array()
        changed, blobs, matches = [], [], 0
        for r in rows.tolist():
            o = int(offs[r])
            value = raw[o:o + int(lens[r])].tobytes()
            masked, k = self._sub(value)
            if k and masked != value:
                changed.append(r)
                blobs.append(masked)
            matches += k
        if changed:
            sizes = np.array([len(b) for b in blobs], dtype=np.int64)
            base = group.source_buffer.copy_string(b"".join(blobs)).offset
            new_offs[changed] = base + np.cumsum(sizes) - sizes
            new_lens[changed] = sizes
        return len(changed), matches

    def process(self, group: PipelineEventGroup) -> None:
        reason = "tier" if self._plan is None else "no_fusion"
        cols = group.columns
        if cols is not None and not group._events:
            skey = self.source_key.decode()
            target = cols.fields.get(skey)
            n = len(cols)
            if target is None and cols.fields:
                _note(self._pipeline, rows_total=n, absent_rows_total=n)
                return
            # no parsed field at all: the raw content spans are the source
            offs, lens = target if target is not None \
                else (cols.offsets, cols.lengths)
            with stage_span("desensitize.host"):
                new_offs = np.array(offs, dtype=np.int32)
                new_lens = np.array(lens, dtype=np.int32)
                rows = np.flatnonzero(new_lens >= 0)
                masked, matches = self._host_rows(group, offs, lens, rows,
                                                  new_offs, new_lens)
                if masked and target is None:
                    cols.offsets, cols.lengths = new_offs, new_lens
                elif masked:
                    cols.set_field(skey, new_offs, new_lens)
            _note(self._pipeline, {reason: len(rows)}, rows_total=n,
                  absent_rows_total=n - len(rows), masked_rows_total=masked,
                  matches_total=matches)
            return
        sb = group.source_buffer
        rows = absent = masked = matches = 0
        for ev in group.events:
            if not hasattr(ev, "get_content"):
                continue
            rows += 1
            v = ev.get_content(self.source_key)
            if v is None:
                absent += 1
                continue
            data = v.to_bytes()
            out, k = self._sub(data)
            matches += k
            if out != data:
                ev.set_content(self.source_key, sb.copy_string(out))
                masked += 1
        _note(self._pipeline, {reason: rows - absent}, rows_total=rows,
              absent_rows_total=absent, masked_rows_total=masked,
              matches_total=matches)

"""processor_classify_url — rule-based URL/category classification on device.

The BASELINE.json scenario "eBPF HTTP/network events → TPU regex URL
classification": each rule is a regex over a source field (default `path`);
the FIRST rule of the list that fully matches names the category, a value no
rule matches takes `DefaultCategory`, an event without the source field gets
no category.

The whole list is ONE automaton (`ops/regex/fuse.py`: the rules' multi-accept
product, determinised and minimised), walked once a row whatever the list's
length:

* **in a fused run** (`fused_stage_spec`), behind a stage that publishes the
  source field as a span column (`processor_parse_regex_tpu`'s capture, a
  JSON member): a ``label`` stage of that run's one device program walks the
  automaton over the span, still device-resident, and hands back the index of
  the first rule that matches; `_fused_apply` turns the column of indices into
  the category field through a table of interned names.  The list must fit
  the device automaton (`FusedDFA.device_ok`: at most `DEVICE_MAX_STATES`
  states) with every rule in it (at most `MAX_PATTERNS`, none on the CPU
  tier); any other list, and any other position in a pipeline, keeps
* **the host tier** (`process`): one pass of the same automaton on the
  byte-table scanner (`FusedSetExec.classify`) and the lowest accept bit.
  Also what a demoted chunk of the fused program runs (`_staged_label`).
* A list the automaton cannot hold whole (a CPU-tier rule, over 32 rules or
  the host's state budget) keeps the per-rule loop: `match_batch` of each
  rule's own engine over what is still unassigned, in order.

`/debug/status` `classify_url` has, by pipeline, where the rows went; the
spans are `classify.apply` (the fused run's install) and `classify.host`.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Tuple

import numpy as np

from ..models import PipelineEventGroup
from ..ops.regex.engine import RegexEngine, get_engine
from ..pipeline.plugin.interface import PluginContext, Processor
from .common import extract_source, stage_span

# /debug/status ``classify_url``: per pipeline, cumulative for the process
COUNTERS = ("rows_total", "label_program_rows_total", "host_rows_total",
            "absent_rows_total", "default_rows_total")
_stats_lock = threading.Lock()
_stats: Dict[str, dict] = {}


def _note(pipeline: str, rule_rows, **deltas: int) -> None:
    with _stats_lock:
        row = _stats.get(pipeline)
        if row is None:
            row = _stats[pipeline] = dict.fromkeys(COUNTERS, 0)
            row["rule_rows_total"] = [0] * len(rule_rows)
        for k, v in deltas.items():
            row[k] += v
        for i, v in enumerate(rule_rows):
            row["rule_rows_total"][i] += v


def status() -> Dict[str, dict]:
    """The ``classify_url`` section of /debug/status: by pipeline, rows
    through the stage (``rows_total``), where a row with the source field
    was labelled — the fused program's ``label`` stage
    (``label_program_rows_total``) or the host (``host_rows_total``) —, rows
    without the field (``absent_rows_total``; the three add up to
    ``rows_total``), rows no rule matched (``default_rows_total``) and rows
    each rule took, by its position in ``Rules`` (``rule_rows_total``)."""
    with _stats_lock:
        return {k: dict(v, rule_rows_total=list(v["rule_rows_total"]))
                for k, v in _stats.items()}


def reset_for_testing() -> None:
    with _stats_lock:
        _stats.clear()


class ProcessorClassifyUrl(Processor):
    name = "processor_classify_url_tpu"
    supports_columnar = True

    def __init__(self) -> None:
        super().__init__()
        self.source_key = b"path"
        self.target_key = "category"
        self.default = b"other"
        self.rules: List[Tuple[bytes, RegexEngine]] = []
        self._pipeline = ""
        #: the list as one automaton, where it holds every rule
        self._set = None
        #: the automaton's distinct accept masks, sorted, and 1 + the first
        #: rule of each (0: none) — a scan's tags become table indices by one
        #: searchsorted
        self._masks = np.zeros(0, dtype=np.uint32)
        self._mask_index = np.zeros(0, dtype=np.int32)
        #: the default's and the rules' names as one blob and the table that
        #: turns 1 + label into a span of it
        self._names = b""
        self._name_off = np.zeros(0, dtype=np.int32)
        self._name_len = np.zeros(0, dtype=np.int32)

    def init(self, config: Dict[str, Any], context: PluginContext) -> bool:
        super().init(config, context)
        self.source_key = config.get("SourceKey", "path").encode()
        self.target_key = config.get("TargetKey", "category")
        self.default = config.get("DefaultCategory", "other").encode()
        for rule in config.get("Rules", []):
            name = rule.get("Name", "")
            pattern = rule.get("Regex", "")
            if not name or not pattern:
                return False
            self.rules.append((name.encode(), get_engine(pattern)))
        if not self.rules:
            return False
        names = [self.default] + [name for name, _ in self.rules]
        self._names = b"".join(names)
        self._name_len = np.array([len(n) for n in names], dtype=np.int32)
        self._name_off = (np.cumsum(self._name_len, dtype=np.int32)
                          - self._name_len)
        from ..ops.kernels.dfa_scan import first_pattern
        from ..ops.regex.fuse import try_build_set
        fs = try_build_set([e.pattern for _, e in self.rules],
                           names=[n.decode("utf-8", "replace")
                                  for n, _ in self.rules])
        if fs is not None and fs.n_fused == len(self.rules):
            self._set = fs
            self._masks = np.unique(fs.fdfa.accept_tags.astype(np.uint32))
            self._mask_index = first_pattern(self._masks) + 1
        self._pipeline = getattr(context, "pipeline_name", "") or ""
        return True

    # -- the fused run ------------------------------------------------------

    def fused_stage_spec(self, ctx):
        """loongresident: the rule list joins a fused pipeline program as
        ONE ``label`` stage — the list's automaton walked over the span
        column a prior member publishes for ``SourceKey`` (or over the
        run's packed rows, where they are the source), the first matching
        rule's index a row out.  Refuses, and keeps the host tier, where
        the source is not statically resident, where the automaton does not
        hold every rule, or where it is over the device's state budget."""
        fs = self._set
        if fs is None or not fs.fdfa.device_ok:
            return None
        binding = ctx.resolve(self.source_key)
        if binding is None:
            return None
        if binding == "source":
            if not ctx.bind_source(self.source_key):
                return None
            bound = None
        else:
            bound = tuple(binding[1:])
        from ..ops import fused_pipeline as fp
        from ..pipeline.fused_chain import FusedMemberStage
        spec = fp.StageSpec(
            "label", fs.fdfa,
            ["label"] + list(fs.fdfa.patterns) + [list(bound or ())],
            staged=self._staged_label, binding=bound,
            label=f"label:{self.name}")
        ctx.note_fields(ctx.n_stages, [self.target_key])
        return FusedMemberStage(spec, self._fused_apply)

    def _staged_label(self, rows, lengths, starts, spanlens) -> np.ndarray:
        """The ``label`` stage of a demoted chunk: the host's scanner over
        the spans of the packed rows."""
        rows = np.ascontiguousarray(rows)
        starts = np.asarray(starts, dtype=np.int64)
        spanlens = np.asarray(spanlens, dtype=np.int32)
        present = spanlens >= 0
        ends = np.minimum(starts + spanlens, np.asarray(lengths))
        offsets = np.arange(len(rows), dtype=np.int64) * rows.shape[1] + starts
        return self._scan(rows.reshape(-1), offsets,
                          np.where(present, ends - starts, 0), present) - 1

    def _fused_apply(self, group, src, out, rowmap):
        with stage_span("classify.apply"):
            index = out[0][rowmap] + 1
            spans = group.columns.fields.get(
                self.source_key.decode("latin-1"))
            if spans is None:           # the run's own rows are the source
                present = src.present[rowmap]
                host = 0
            else:
                present = spans[1] >= 0
                host = self._decide_on_host(group, src, rowmap, spans, index)
            self._install(group, index, present, host_rows=host)
        return rowmap

    def _decide_on_host(self, group, src, rowmap, spans, index) -> int:
        """Rows whose spans the device did not produce (a json_fields
        producer handed them to the host's emitter, which installed the
        field since, maybe in bytes it appended to the arena): the
        program's label says nothing of them, the host's scanner decides
        them in place.  Returns how many it took."""
        if src.undecided is None:
            return 0
        rows = np.nonzero(src.undecided[rowmap] & (spans[1] >= 0))[0]
        if len(rows):
            index[rows] = self._scan(group.source_buffer.as_array(),
                                     spans[0][rows], spans[1][rows])
        return len(rows)

    # -- the host tier ------------------------------------------------------

    def _scan(self, arena, offsets, lengths, present=None) -> np.ndarray:
        """1 + the first rule that fully matches each span (0: none), by
        one pass of the list's automaton; a row that is not ``present``
        (where given: an absent span scans as an empty one) is 0."""
        tags = self._set.classify(arena, offsets, lengths)
        index = self._mask_index[np.searchsorted(self._masks, tags)]
        if present is not None:
            index[~present] = 0
        return index

    def _scan_by_rule(self, src) -> np.ndarray:
        """The same for a list the automaton does not hold whole: each
        rule's own engine over what is still unassigned, in order."""
        index = np.zeros(len(src.offsets), dtype=np.int32)
        unassigned = src.present.copy()
        for k, (_name, engine) in enumerate(self.rules):
            idx = np.nonzero(unassigned)[0]
            if not len(idx):
                break
            ok = engine.match_batch(src.arena, src.offsets[idx],
                                    src.lengths[idx])
            hit = idx[ok]
            index[hit] = k + 1
            unassigned[hit] = False
        return index

    def _install(self, group, index: np.ndarray, present: np.ndarray,
                 host_rows=None) -> None:
        """The category column from ``index`` (1 + the rule, 0 for none):
        one interned copy of the names a group, two table reads.  Of the
        present rows the host decided ``host_rows`` (None: all of them) and
        a fused program's label stage the rest."""
        base = group.source_buffer.copy_string(self._names).offset
        lens = self._name_len[index]
        n_present = len(index)
        if not present.all():
            lens[~present] = -1
            n_present = int(np.count_nonzero(present))
        group.columns.set_field(self.target_key,
                                self._name_off[index] + np.int32(base), lens)
        counts = np.bincount(index, minlength=len(self.rules) + 1)
        absent = len(index) - n_present
        if host_rows is None:
            host_rows = n_present
        _note(self._pipeline, counts[1:].tolist(), rows_total=len(index),
              label_program_rows_total=n_present - host_rows,
              host_rows_total=host_rows, absent_rows_total=absent,
              default_rows_total=int(counts[0]) - absent)

    def process(self, group: PipelineEventGroup) -> None:
        src = extract_source(group, self.source_key)
        if src is None:
            return
        n = len(src.offsets)
        if n == 0:
            return

        if src.columnar:
            with stage_span("classify.host"):
                if self._set is not None:
                    index = self._scan(src.arena, src.offsets, src.lengths,
                                       src.present)
                else:
                    index = self._scan_by_rule(src)
                self._install(group, index, src.present)
            return

        sb = group.source_buffer
        cat_views = [sb.copy_string(name) for name, _ in self.rules]
        default_view = sb.copy_string(self.default)
        for ev in group.events:
            if not hasattr(ev, "get_content"):
                continue
            v = ev.get_content(self.source_key)
            if v is None:
                continue
            data = v.to_bytes()
            label = default_view
            for (name, engine), view in zip(self.rules, cat_views):
                if engine._re.fullmatch(data):
                    label = view
                    break
            ev.set_content(self.target_key.encode(), label)

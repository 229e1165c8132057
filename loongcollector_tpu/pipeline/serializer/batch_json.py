"""Batched NDJSON assembly shared by the JSON-family sinks (loongshard).

Before this module, JsonSerializer and four flushers (clickhouse / doris /
elasticsearch / loki) each ran the same loop: materialise a Python dict per
event, then ``json.dumps`` per row.  At pipeline rates that is the dominant
serialize cost — every field pays a bytes→str decode, a dict insert and a
re-encode, even though for columnar groups the values are untouched spans
of the SourceBuffer arena.

The fast path assembles output bytes once per group in native code
(``lct_ndjson_serialize``): cached group-tag prefix, cached per-column key
fragments, values escaped straight out of the arena.  Python only decides
eligibility — groups whose spans may hold non-ASCII bytes fall back to the
canonical dict path, because ``json.dumps`` + ``decode("utf-8", "replace")``
semantics for invalid UTF-8 belong to CPython, not to a C re-implementation.

Output is byte-identical to the dict path — ``json.dumps(obj,
ensure_ascii=False)`` with default separators — pinned by golden tests
(tests/test_batch_json.py).
"""

from __future__ import annotations

import json
from typing import Dict, List, Optional, Tuple

import numpy as np

from ... import native
from ...models import PipelineEventGroup
from .event_dicts import iter_event_dicts

TS_NONE = native.NDJSON_TS_NONE
TS_EPOCH = native.NDJSON_TS_EPOCH
TS_ISO8601 = native.NDJSON_TS_ISO8601

def dumps_row(obj: Dict[str, object]) -> bytes:
    """The one canonical row encoder every JSON sink shares (identical to
    the four ``json.dumps(obj, ensure_ascii=False)`` copies it replaced)."""
    return json.dumps(obj, ensure_ascii=False).encode()


def decoded_tags(group: PipelineEventGroup) -> Dict[str, str]:
    """Group tags in the exact shape the dict path folds into every row."""
    return {k.decode("utf-8", "replace"): str(v)
            for k, v in group.tags.items()}


_frag_cache: Dict[str, bytes] = {}
_prefix_cache: Dict[Tuple[Tuple[str, str], ...], bytes] = {}


def _field_frag(name: str) -> bytes:
    """``"name": "`` — cached; schemas repeat for every group."""
    frag = _frag_cache.get(name)
    if frag is None:
        frag = (json.dumps(name, ensure_ascii=False) + ': "').encode()
        if len(_frag_cache) > 4096:      # unbounded schemas must not leak
            _frag_cache.clear()
        _frag_cache[name] = frag
    return frag


def tag_prefix(tags: Dict[str, str]) -> bytes:
    """``{"tag": "value"`` — the per-group constant head of every row
    (no trailing separator; the native writer adds ``, `` before the first
    member it appends).  Cached: steady-state pipelines re-emit identical
    tag sets for every group."""
    key = tuple(tags.items())
    pre = _prefix_cache.get(key)
    if pre is None:
        inner = ", ".join(
            f"{json.dumps(k, ensure_ascii=False)}: "
            f"{json.dumps(v, ensure_ascii=False)}" for k, v in tags.items())
        pre = ("{" + inner).encode()
        if len(_prefix_cache) > 1024:
            _prefix_cache.clear()
        _prefix_cache[key] = pre
    return pre


def _columnar_layout(group: PipelineEventGroup):
    """(names, offs [F,n] i32, lens [F,n] i32, tss) for the fast path, or
    None when the group is not columnar / the layout is not fast-safe.
    Field order matches iter_event_dicts exactly."""
    cols = group.columns
    if cols is None or group._events:
        return None
    fields = cols.fields or {}
    names = [n for n in fields if n != "_partial_"]
    spans = [fields[n] for n in names]
    if not cols.content_consumed and "content" not in fields:
        names.insert(0, "content")
        spans.insert(0, (cols.offsets, cols.lengths))
    if not names:
        return None
    if any(not isinstance(n, str) for n in names):
        return None
    try:
        offs = np.stack([np.asarray(s[0], dtype=np.int32) for s in spans])
        lens = np.stack([np.asarray(s[1], dtype=np.int32) for s in spans])
    except ValueError:
        return None
    return names, offs, lens, cols.timestamps


def _spans_are_ascii(group: PipelineEventGroup, offs: np.ndarray,
                     lens: np.ndarray) -> bool:
    """True when every present span is single-byte UTF-8 (no byte >=
    0x80; a span with one takes the CPython path, so that invalid
    sequences get codec-identical treatment).  Cheap max() over the arena
    answers the common machine-log case in one SIMD pass; an arena that does hold high bytes (a JSON group's
    decoded escapes sit in its side arena whether or not their rows
    survived a filter) pays one compare for their positions and a binary
    search per present span — the cost follows the spans that are left,
    not the arena."""
    raw = group.source_buffer.raw
    if len(raw) == 0:
        return True
    arena = np.frombuffer(raw, dtype=np.uint8, count=len(raw))
    if int(arena.max()) < 0x80:
        return True
    high = np.flatnonzero(arena.view(np.int8) < 0)
    present = lens >= 0
    o = offs[present].astype(np.int64)
    e = o + lens[present]
    return bool((np.searchsorted(high, o) == np.searchsorted(high, e)).all())


def _native_args(group: PipelineEventGroup, ts_key: Optional[str],
                 ts_mode: int, ts_first: bool, head: bytes,
                 check_ascii: bool):
    """The arguments of native.ndjson_serialize from the arena on, for a
    group the native assembler may take; None ⇒ the canonical dict path."""
    layout = _columnar_layout(group)
    if layout is None:
        return None
    names, offs, lens, tss = layout
    tags = decoded_tags(group)
    if ts_key is not None and (ts_key in names or ts_key in tags):
        # setdefault semantics: an existing field/tag wins — rare enough
        # that the dict path handles it wholesale
        return None
    if any(n in tags for n in names):
        # a field overwrites the same-named tag IN PLACE in the dict path;
        # the flat fast layout cannot reproduce that ordering
        return None
    if check_ascii and not _spans_are_ascii(group, offs, lens):
        return None
    prefix = head + tag_prefix(tags)
    ts_frag = b""
    if ts_key is not None and ts_mode != TS_NONE:
        ts_frag = (json.dumps(ts_key, ensure_ascii=False) + ": ").encode()
    else:
        ts_mode = TS_NONE
    return (np.frombuffer(group.source_buffer.raw, dtype=np.uint8,
                          count=len(group.source_buffer.raw)),
            np.asarray(tss, dtype=np.int64),
            tuple(_field_frag(n) for n in names),
            offs, lens, prefix, bool(tags), ts_frag, ts_mode, ts_first)


def native_group_rows(group: PipelineEventGroup,
                      ts_key: Optional[str],
                      ts_mode: int = TS_EPOCH,
                      ts_first: bool = False,
                      suffix: bytes = b"\n",
                      head: bytes = b"",
                      ) -> Optional[memoryview]:
    """One group's NDJSON rows via the native assembler; None ⇒ the caller
    must run the canonical dict path for this group.  ``head`` is prepended
    to every row before the JSON object (ES bulk action lines)."""
    args = _native_args(group, ts_key, ts_mode, ts_first, head, True)
    if args is None:
        return None
    return native.ndjson_serialize(*args, suffix=suffix)


def native_group_append(group: PipelineEventGroup, path: str, scratch,
                        ts_key: Optional[str], ts_mode: int = TS_EPOCH,
                        ts_first: bool = False):
    """`native_group_rows` appended to the file at ``path`` in the same
    native call (native.ndjson_serialize_append, whose results these are);
    the arena's bytes are checked there, all of them, not span by span —
    a group it declines may still be one `native_group_rows` takes."""
    args = _native_args(group, ts_key, ts_mode, ts_first, b"", False)
    if args is None:
        return None, scratch
    return native.ndjson_serialize_append(path, scratch, *args)


def ndjson_payload(groups: List[PipelineEventGroup],
                   ts_key: Optional[str] = None,
                   ts_mode: int = TS_EPOCH,
                   ) -> Optional[bytes]:
    """The shared NDJSON payload builder (clickhouse / doris): one JSON
    object per line, ``obj.setdefault(ts_key, ts)`` semantics, trailing
    newline after every row.  Columnar groups take the native zero-copy
    assembly; everything else rides the canonical dict path."""
    parts: List = []
    empty = True
    for g in groups:
        fast = native_group_rows(g, ts_key, ts_mode=ts_mode, ts_first=False)
        if fast is not None:
            if len(fast):
                empty = False
                parts.append(fast)
            continue
        for ts, obj in iter_event_dicts(g):
            if ts_key is not None:
                obj.setdefault(ts_key, ts)
            parts.append(dumps_row(obj))
            parts.append(b"\n")
            empty = False
    if empty:
        return None
    return b"".join(parts)

#!/usr/bin/env python
"""loongresident equivalence gate (scripts/lint.sh + tier-1).

The fused pipeline program must be a pure execution-plan change: for
every pipeline family — regex, regex+grok, delimiter, json, multiline —
running the SAME processor chain with stage fusion forced on
(``LOONG_FUSED=1``: one fused device program per batch slot) and forced
off (``LOONG_FUSED=0``: the per-stage dispatch path, which on this host
routes through the native/host tiers) must produce BYTE-IDENTICAL
groups: same surviving rows, same field spans, same kept/renamed
sources, same parse_ok vector.  Identity is compared as a blake2b digest
over the canonical column snapshot.

Families where fusion engages (a planned run of ≥ 2 stages exists) also
assert that the fused side really did fuse — one device dispatch for the
run — so the gate cannot rot into comparing the staged path to itself.
The json family fuses since PR 27 (filter → ``json_fields``: the device
stage emits the field spans, rows it cannot prove go back to the native
emitter); a lone processor_parse_json_tpu still has no run, which
tests/test_json_fields.py pins.

Exit 0 = identical everywhere; exit 1 = any digest mismatch (printed
per family).
"""

from __future__ import annotations

import hashlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

from loongcollector_tpu import models  # noqa: E402
from loongcollector_tpu.models import (ColumnarLogs,  # noqa: E402
                                       PipelineEventGroup, SourceBuffer)
from loongcollector_tpu.ops import fused_pipeline as fp  # noqa: E402
from loongcollector_tpu.ops.device_plane import DevicePlane  # noqa: E402
from loongcollector_tpu.pipeline.pipeline import \
    CollectionPipeline  # noqa: E402

REGEX_LINES = [b"abc 123", b"nope!", b"zz 15", b"yy 25", b"q 1",
               b"mixed 9x", b"deep 1000", b"a 0", b"longword 111111"]
DELIM_LINES = [b"ab,cd,ef", b"zz,1,2", b"NOPE,x,y", b"q,w", b"a,b,c,d",
               b",,", b"x,,z"]
GROK_LINES = [b"abc 123", b"abc def", b"!!", b"zz 9", b"word word",
              b"n 0x"]
JSON_LINES = [b'{"a": "x", "n": 1}', b'not json', b'{"a": "y", "n": 2}',
              b'{"a": "z\\tq", "extra": true}']
ML_LINES = [b"[1] start line", b"  at frame one", b"  at frame two",
            b"[2] other", b"loose", b"[3] tail", b"  at deep"]

FAMILIES = [
    ("regex", REGEX_LINES, [
        {"Type": "processor_filter_native",
         "Include": {"content": r"[a-z]+ \d+"}},
        {"Type": "processor_parse_regex_tpu",
         "Regex": r"([a-z]+) (\d+)", "Keys": ["word", "num"]},
        {"Type": "processor_filter_native", "Include": {"num": r"1\d*"}},
    ], True),
    ("delimiter", DELIM_LINES, [
        {"Type": "processor_filter_native",
         "Include": {"content": r"[a-z]*,.*"}},
        {"Type": "processor_parse_delimiter_tpu", "Separator": ",",
         "Keys": ["a", "b", "c"]},
    ], True),
    ("regex+grok", GROK_LINES, [
        {"Type": "processor_filter_native",
         "Include": {"content": r"\w+ .*"}},
        {"Type": "processor_grok",
         "Match": [r"%{WORD:w} %{INT:n}", r"%{WORD:w} %{WORD:v}"]},
    ], None),   # engagement depends on the grok set fusing on this host
    ("json", JSON_LINES, [
        {"Type": "processor_filter_native",
         "Include": {"content": r"\{.*"}},
        {"Type": "processor_parse_json_tpu"},
    ], True),
    ("multiline", ML_LINES, [
        {"Type": "processor_split_multiline_log_string_native",
         "Multiline": {"StartPattern": r"\[\d+\] .*",
                       "ContinuePattern": r"\s+.*"}},
        {"Type": "processor_parse_regex_tpu",
         "Regex": r"(?s)\[(\d+)\] (.*)", "Keys": ["id", "body"]},
    ], None),
]


def make_group(lines) -> PipelineEventGroup:
    blob = b"".join(lines)
    sb = SourceBuffer(len(blob) + 256)
    g = PipelineEventGroup(sb)
    views = [sb.copy_string(ln) for ln in lines]
    g.set_columns(ColumnarLogs(
        offsets=np.array([v.offset for v in views], np.int32),
        lengths=np.array([len(ln) for ln in lines], np.int32),
        timestamps=np.full(len(lines), 1700000002, np.int64)))
    return g


def digest(group: PipelineEventGroup) -> str:
    cols = group.columns
    arena = group.source_buffer.as_array()
    h = hashlib.blake2b(digest_size=16)
    n = len(cols)
    h.update(b"n=%d;consumed=%d;" % (n, int(cols.content_consumed)))
    if not cols.content_consumed:
        for i in range(n):
            o, ln = int(cols.offsets[i]), int(cols.lengths[i])
            h.update(b"c:")
            h.update(arena[o:o + ln].tobytes())
            h.update(b";")
    for k, (offs, lens) in sorted(cols.fields.items()):
        h.update(b"f:" + k.encode() + b";")
        for i in range(n):
            ln = int(lens[i])
            if ln < 0:
                h.update(b"\x00-")
            else:
                h.update(arena[int(offs[i]):int(offs[i]) + ln].tobytes())
            h.update(b";")
    if cols.parse_ok is not None:
        h.update(b"ok:" + np.asarray(cols.parse_ok, np.uint8).tobytes())
    return h.hexdigest()


def run_family(name, lines, processors, fused: bool):
    os.environ["LOONG_FUSED"] = "1" if fused else "0"
    DevicePlane.reset_for_testing()
    p = CollectionPipeline()
    config = {"inputs": [], "processors": processors,
              "flushers": [{"Type": "flusher_stdout"}]}
    assert p.init(f"fused-eq-{name}-{int(fused)}", config), name
    plane = DevicePlane.instance()
    g = make_group(lines)
    fin = p.process_begin([g])
    while fin is not None:
        fin = fin()
    engaged = bool(p._fused_runs) and fused and plane.dispatched_total() \
        and any(r.program().dispatch_count for r in p._fused_runs)
    return digest(g), bool(p._fused_runs), engaged


def main() -> int:
    models.set_columnar_enabled(True)
    failures = 0
    engaged_total = 0
    for name, lines, processors, want_fusable in FAMILIES:
        fp.reset_for_testing()
        d_fused, planned, engaged = run_family(name, lines, processors,
                                               fused=True)
        d_staged, _, _ = run_family(name, lines, processors, fused=False)
        status = "fused" if engaged else "per-stage"
        if d_fused != d_staged:
            print(f"FAIL [{name}] fused {d_fused} != staged {d_staged}")
            failures += 1
            continue
        if want_fusable is True and not engaged:
            print(f"FAIL [{name}] expected a fused run to engage "
                  f"(planned={planned})")
            failures += 1
            continue
        if want_fusable is False and planned:
            print(f"FAIL [{name}] must not plan a fused run")
            failures += 1
            continue
        engaged_total += int(engaged)
        print(f"ok [{name}] byte-identical ({status})")
    if failures:
        print(f"fused equivalence gate: {failures} family(ies) FAILED")
        return 1
    print(f"fused equivalence gate: {len(FAMILIES)} families "
          f"byte-identical, {engaged_total} with fusion engaged — OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())

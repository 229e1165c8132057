"""The comparison that decides ``correct``.

Two parts.  The tailer held EVERY sink record's sequence number to the one
the plain reference expects at that row while the run went (every line's fate
settled exactly once, in per-source order).  Here, once the window has closed
and the agent is gone, the sampled reads are compared field by field: the
input line is made again from the seed, the plain reference says what the
deployment must emit for it, and the sink's record has to be that.

Every number compared is exact, so every limit is 0 (``records_short`` counts
how far the comparison fell short of the records it has to cover).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

#: a run compares at least this many records field by field
MIN_RECORDS = 2000

#: faults the controls plant in the stream between the sink and the checks
STREAM_FAULTS = ("drop_row", "dup_row", "swap_rows", "alter_field",
                 "time_off", "stall")


def keep_mask(source, reference) -> np.ndarray:
    """For each template of the line source: does the plain reference keep
    its lines (a sink record) or drop them (none)?"""
    return np.array([reference.expected(
        source.templates[k].tobytes().rstrip(b"\n")) is not None
        for k in range(source.pool)])


def compare_samples(run_dir: str, tail, source, reference,
                    epoch_lo: float) -> dict:
    """Field-by-field comparison of the tailer's sampled reads."""
    compared = bad_record = bad_time = 0
    first_bad = []
    epoch_hi = time.time() + 2
    with open(os.path.join(run_dir, "tail.samples"), "rb") as f:
        for _row0, off, size in tail["sample_index"].tolist():
            f.seek(off)
            data = f.read(size)
            seqs = source.seqs_in(data)
            records = data.split(b"\n")[:-1]
            if len(records) != seqs.size or not seqs.size:
                bad_record += max(len(records), 1)
                continue
            lines = source.block_at(seqs)
            for k, raw in enumerate(records):
                seq = int(seqs[k])
                compared += 1
                want = reference.expected(lines[k].tobytes()[:-1])
                try:
                    got = json.loads(raw)
                except ValueError:
                    got = None
                if want is None or not isinstance(got, dict):
                    bad_record += 1
                    if len(first_bad) < 3:
                        first_bad.append(f"line {seq}: want {_clip(want)} "
                                         f"got {_clip(raw)}")
                    continue
                want_rec, want_time = want
                got_time = got.pop("__time__", None)
                if got != want_rec:
                    bad_record += 1
                    if len(first_bad) < 3:
                        first_bad.append(f"line {seq}: want {_clip(want_rec)} "
                                         f"got {_clip(got)}")
                elif want_time is None:
                    if not isinstance(got_time, int) or \
                            not epoch_lo - 2 <= got_time <= epoch_hi:
                        bad_time += 1
                elif got_time != want_time:
                    bad_time += 1
                    if len(first_bad) < 3:
                        first_bad.append(f"line {seq}: __time__ {got_time} "
                                         f"want {want_time}")
    return {"compared": compared, "bad_record": bad_record,
            "bad_time": bad_time, "first_bad": first_bad}


def _clip(obj, width: int = 240) -> str:
    s = obj.decode("latin-1") if isinstance(obj, bytes) else json.dumps(obj)
    return s if len(s) <= width else s[:width] + f"... ({len(s)} chars)"


def verdict(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def render(checks: dict) -> str:
    """One line per number compared, beside its limit."""
    return "\n".join(
        f"check {name}: {c['value']} (limit {c['limit']})"
        + ("" if c["value"] <= c["limit"] else "  <-- FAILS")
        for name, c in checks.items())

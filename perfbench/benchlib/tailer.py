#!/usr/bin/env python3
"""The sink tailer: a process of its own that watches the sink file grow.

    python perfbench/benchlib/tailer.py <spec.json>

It reads the sink as it is appended (polling at twice a millisecond's rate),
and for every read that completes at least one record it notes the time, the
rows so far and the last line settled.  It holds EVERY record's sequence
number to the one expected at that row — every line's fate exactly once, in
order — and keeps the bytes of a seeded sample of reads (and always the
latest one) for the field-by-field comparison with the plain reference, which
runs once the window has closed.

Shared with the parent through the run directory:

    tail.progress   16 bytes, mmap: last line settled, rows seen (int64 each)
    tail.stop       the parent's request to finish (read to EOF, then exit)
    tail.arm        written by the parent when the window opens: a planted
                    fault acts from then on
    tail.npz        the result: read times, rows, last line, bytes; counts
    tail.samples    the sampled reads' bytes, indexed in tail.npz

``fault`` in the spec plants a fault in the stream between the sink and the
checks — the benchmark's control, see README.md; a benchmark run has none.
"""

from __future__ import annotations

import json
import mmap
import os
import re
import struct
import sys
import time

import numpy as np

if not __package__:          # run as a script: the library is one level up
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
from benchlib import check  # noqa: E402
from benchlib import spec as specmod  # noqa: E402

READ_BYTES = 8 << 20
IDLE_SLEEP_S = 0.0005
SAMPLE_CAP_BYTES = 96 << 20
EXPECT_BLOCK = 1 << 14


class ExpectedRows:
    """Row r of the sink is line ``seq(r)``: the r-th line the plain
    reference keeps.  Computed from the seed alone, a small block at a time
    (a block costs under a millisecond, so the tailer's clock stays fine),
    and forgotten once the rows have passed."""

    def __init__(self, source, reference):
        self.source = source
        self.keep = check.keep_mask(source, reference)
        self._seqs = np.empty(0, np.int64)
        self._base = 0               # the sink row of _seqs[0]
        self._next_line = 0

    def take(self, row: int, n: int) -> np.ndarray:
        """The lines expected at sink rows ``row .. row+n``."""
        if row - self._base > 4 * EXPECT_BLOCK:
            self._seqs = self._seqs[row - self._base:]
            self._base = row
        while self._base + self._seqs.size < row + n:
            a = self._next_line
            kept = np.flatnonzero(
                self.keep[self.source.template_of(a, EXPECT_BLOCK)]) + a
            self._seqs = np.concatenate([self._seqs, kept])
            self._next_line = a + EXPECT_BLOCK
        return self._seqs[row - self._base:row - self._base + n]


def plant(fault: str, data: bytes) -> bytes:
    """``data`` (whole records) with the fault planted in it."""
    lines = data.split(b"\n")[:-1]
    if fault == "drop_row":
        del lines[len(lines) // 2]
    elif fault == "dup_row":
        lines.insert(len(lines) // 2, lines[len(lines) // 2])
    elif fault == "swap_rows" and len(lines) > 1:
        k = len(lines) // 2
        lines[k - 1], lines[k] = lines[k], lines[k - 1]
    elif fault == "alter_field":
        lines = [re.sub(rb'("status": ")(\d)', lambda m: m.group(1) + bytes(
            [48 + (m.group(2)[0] - 47) % 10]), ln, count=1) for ln in lines]
    elif fault == "time_off":
        lines = [re.sub(rb'("__time__": )(\d+)', lambda m: m.group(1) + str(
            int(m.group(2)) + 1).encode(), ln, count=1) for ln in lines]
    return b"\n".join(lines) + b"\n"


def run(doc: dict) -> None:
    run_dir = doc["run_dir"]
    cfg = doc["config"]
    source = specmod.load_module("sources", cfg["source"]["kind"]).make(
        cfg["source"], doc["seed"])
    reference = specmod.load_module("references", cfg["reference"]["kind"]) \
        .make(cfg["reference"])
    expected = ExpectedRows(source, reference)
    expected.take(0, 1)
    fault = doc.get("fault")
    arm_path = os.path.join(run_dir, "tail.arm")
    armed = False
    sample_mod = max(1, int(round(1.0 / float(doc["sample_share"]))))
    rng = np.random.default_rng(doc["seed"])

    prog_path = os.path.join(run_dir, "tail.progress")
    with open(prog_path, "wb") as f:
        f.write(struct.pack("<qq", -1, 0))
    prog_f = open(prog_path, "r+b")
    prog = mmap.mmap(prog_f.fileno(), 16)
    stop_path = os.path.join(run_dir, "tail.stop")
    samples = open(os.path.join(run_dir, "tail.samples"), "wb")

    sink = doc["sink"]
    while not os.path.exists(sink):
        if os.path.exists(stop_path):
            break
        time.sleep(0.001)
    fd = os.open(sink, os.O_RDONLY) if os.path.exists(sink) else None

    t_read, rows_end, last_seq, bytes_end = [], [], [], []
    sample_index = []            # (first row, offset in tail.samples, bytes)
    latest = None                # the latest read, always compared
    rows = nbytes = sample_bytes = sample_rows = 0
    bad_seq = bad_newlines = 0
    first_bad = []
    carry = b""
    fault_done = False
    stalled = False
    eof_after_stop = 0
    while fd is not None:
        data = b"" if stalled else os.read(fd, READ_BYTES)
        if not data:
            if os.path.exists(stop_path):
                eof_after_stop += 1
                if eof_after_stop >= 3:
                    break
            time.sleep(IDLE_SLEEP_S)
            continue
        now = time.monotonic()
        nbytes += len(data)
        buf = carry + data
        cut = buf.rfind(b"\n") + 1
        carry = buf[cut:]
        if not cut:
            continue
        whole = buf[:cut]
        armed = armed or (fault is not None and os.path.exists(arm_path))
        if armed:
            if fault == "stall":
                stalled = True
                continue
            if fault in ("alter_field", "time_off") or not fault_done:
                whole = plant(fault, whole)
                fault_done = True
        seqs = source.seqs_in(whole)
        n = int(seqs.size)
        if whole.count(b"\n") != n:
            bad_newlines += 1
        want = expected.take(rows, n)
        wrong = int(np.count_nonzero(seqs != want)) if n else 0
        if wrong:
            bad_seq += wrong
            if len(first_bad) < 5:
                k = int(np.flatnonzero(seqs != want)[0])
                first_bad.append([rows + k, int(want[k]), int(seqs[k])])
        if n:
            rows += n
            t_read.append(now)
            rows_end.append(rows)
            last_seq.append(int(seqs[-1]))
            bytes_end.append(nbytes - len(carry))
            prog[:16] = struct.pack("<qq", int(seqs[-1]), rows)
            latest = (rows - n, whole)
            # the first reads are all kept, until the comparison has the
            # records it needs at the least; from then on the seeded share
            if (sample_rows < check.MIN_RECORDS
                    or rng.integers(sample_mod) == 0) and \
                    sample_bytes + len(whole) <= SAMPLE_CAP_BYTES:
                sample_index.append([rows - n, sample_bytes, len(whole)])
                samples.write(whole)
                sample_bytes += len(whole)
                sample_rows += n
                latest = None
    if latest is not None:
        sample_index.append([latest[0], sample_bytes, len(latest[1])])
        samples.write(latest[1])
    samples.close()
    if fd is not None:
        os.close(fd)
    np.savez(os.path.join(run_dir, "tail.npz"),
             t=np.asarray(t_read, np.float64),
             rows_end=np.asarray(rows_end, np.int64),
             last_seq=np.asarray(last_seq, np.int64),
             bytes_end=np.asarray(bytes_end, np.int64),
             sample_index=np.asarray(sample_index, np.int64).reshape(-1, 3),
             counts=np.asarray([rows, nbytes, bad_seq, bad_newlines,
                                len(carry)], np.int64),
             first_bad=np.asarray(first_bad, np.int64).reshape(-1, 3))
    prog.close()
    prog_f.close()


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        run(json.load(f))

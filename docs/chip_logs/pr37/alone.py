#!/usr/bin/env python3
"""The timestamp column of one 1,024-row group alone on this host (no agent, no other thread): the numpy
column path, the native call through the CDLL handle (lets go of the interpreter lock) and through the
PyDLL handle (keeps it), and the same three while two other Python threads want the lock — one spinning in
bytecode (the 5 ms switch interval's worst case), one in numpy calls that let go of it.
Prints microseconds a group: least and median of `--rounds` rounds of 200 groups.  Touches no JAX."""
import argparse
import ctypes
import os
import random
import statistics
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))))
import numpy as np

from loongcollector_tpu import native
from loongcollector_tpu.models import PipelineEventGroup, SourceBuffer
from loongcollector_tpu.models.event_group import ColumnarLogs
from loongcollector_tpu.pipeline.plugin.interface import PluginContext
from loongcollector_tpu.processor import parse_timestamp as pt

APACHE = "%d/%b/%Y:%H:%M:%S %z"
MONTHS = "Jan Feb Mar Apr May Jun Jul Aug Sep Oct Nov Dec".split()


def group(seed, rows=1024):
    """A columnar group as the regex leaves it: 512-byte lines, `time` one column of a [rows, 9] matrix."""
    rng = random.Random(seed)
    sb = SourceBuffer()
    lines = []
    for i in range(rows):
        stamp = "%02d/%s/2024:13:%02d:%02d +0000" % (10, MONTHS[4], 7 + (i * 3) // rows, rng.randrange(60))
        head = "10.0.0.%d - - [%s] \"GET /" % (rng.randrange(255), stamp)
        lines.append((head + "x" * (511 - len(head)) + "\n").encode())
    view = sb.copy_string(b"".join(lines))
    offs = view.offset + np.arange(rows, dtype=np.int32) * 512
    cols = ColumnarLogs(offs, np.full(rows, 511, dtype=np.int32))
    off_mat = np.zeros((rows, 9), dtype=np.int32)
    len_mat = np.zeros((rows, 9), dtype=np.int32)
    off_mat[:, 3] = offs + [ln.index(b"[") + 1 for ln in lines]
    len_mat[:, 3] = 26
    if rows > 100:
        len_mat[rng.sample(range(rows), rows // 100), 3] = -1      # the lines the pattern rejects
    cols.set_fields_matrix(["ip", "ident", "user", "time", "method", "url", "proto", "status", "size"],
                           off_mat, len_mat)
    cols.content_consumed = True
    g = PipelineEventGroup(sb)
    g.set_columns(cols)
    return g


def processor():
    p = pt.ProcessorParseTimestamp()
    assert p.init({"SourceKey": "time", "SourceFormat": APACHE}, PluginContext("alone"))
    assert p._plan is not None
    return p


def timed(p, groups, rounds):
    per = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for g in groups:
            p.process(g)
        per.append((time.perf_counter() - t0) / len(groups) * 1e6)
    return min(per), statistics.median(per)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=15)
    args = ap.parse_args()
    lib = native.get_lib()
    groups = [group(s) for s in range(200)]
    want = None
    real = native.timestamp_column
    handles = {"PyDLL": lib.lct_timestamp_column}     # the program's own
    cdll = ctypes.CDLL(native._so_path()).lct_timestamp_column
    cdll.restype, cdll.argtypes = handles["PyDLL"].restype, handles["PyDLL"].argtypes
    handles["CDLL"] = cdll
    stop = threading.Event()

    def spin():
        x = 0
        while not stop.is_set():
            x += 1

    def numpy_calls():
        a = np.arange(200_000)
        while not stop.is_set():
            a.sum()

    for contended in (False, True):
        threads = [threading.Thread(target=f, daemon=True) for f in (spin, numpy_calls)] if contended else []
        for t in threads:
            t.start()
        for name in ("numpy", "CDLL", "PyDLL"):
            if name == "numpy":
                native.timestamp_column = lambda *a, **k: None
            else:
                native.timestamp_column = real
                lib.lct_timestamp_column = handles[name]
            p = processor()
            for g in groups:
                g.columns.timestamps[:] = 0
            p.process(groups[0])
            got = timed(p, groups, args.rounds)
            ts = np.concatenate([g.columns.timestamps for g in groups])
            if want is None:
                want = ts.copy()
            assert (ts == want).all() and (ts != 0).sum() == len(ts) - 200 * 10
            print(f"{'beside two threads' if contended else 'alone':>18} {name:>6}: "
                  f"least {got[0]:8.1f} us a group, median {got[1]:8.1f}", flush=True)
        stop.set()
        for t in threads:
            t.join(5)
        stop.clear()
    native.timestamp_column = real
    lib.lct_timestamp_column = handles["PyDLL"]


if __name__ == "__main__":
    main()

"""File read checkpoints: JSON dump of per-file offsets.

Reference: core/file_server/checkpoint/CheckPointManager.{h,cpp} (h:99-140) —
entries are keyed by DevInode (not path), carrying path + signature + offset,
dumped periodically (application/Application.cpp:384) and restored on start.
Keying by (dev, inode) is what makes rename+recreate rotation safe: the
rotated reader and the new reader at the same path own distinct entries.

v3 (loongcrash): `offset` is the *durable* offset — the acked-bytes
low-watermark from runner/ack_watermark.py for file-server-registered
sources, the read offset for everything else — and `read_offset` records
where reading actually stood (rotation/backlog introspection).  Restoring
seeks to `offset`, so a crash re-reads exactly the unacked window:
at-least-once, never loss.  v1/v2 files load unchanged (offset doubles as
read_offset).
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
import time
from typing import Dict, Optional, Tuple

from ... import trace
from ...runner import ack_watermark
from .reader import ReaderCheckpoint


class CheckPointManager:
    def __init__(self, path: str = ""):
        self.path = path
        self._checkpoints: Dict[Tuple[int, int], ReaderCheckpoint] = {}
        self._lock = threading.Lock()
        self.last_dump = 0.0
        self.quarantined_loads = 0

    @staticmethod
    def _key(cp: ReaderCheckpoint) -> Tuple[int, int]:
        return (cp.dev, cp.inode)

    def update(self, cp: ReaderCheckpoint) -> None:
        with self._lock:
            self._checkpoints[self._key(cp)] = cp

    def get(self, dev: int, inode: int) -> Optional[ReaderCheckpoint]:
        with self._lock:
            return self._checkpoints.get((dev, inode))

    def get_by_path(self, path: str) -> Optional[ReaderCheckpoint]:
        """Path lookup for callers that only know the path (e.g. status
        introspection). Reads prefer dev/inode: with rotation several
        entries may share a path; returns the most recently updated."""
        with self._lock:
            best = None
            for cp in self._checkpoints.values():
                if cp.path == path and (
                        best is None or cp.update_time > best.update_time):
                    best = cp
            return best

    def remove(self, dev: int, inode: int) -> None:
        with self._lock:
            self._checkpoints.pop((dev, inode), None)

    def dump(self) -> None:
        if not self.path:
            return
        with self._lock:
            entries = {}
            for (dev, ino), cp in self._checkpoints.items():
                # the persisted offset is the acked-bytes low-watermark for
                # sources the file server registered; bare readers fall back
                # to the read offset (seed semantics) inside durable_offset
                durable = ack_watermark.durable_offset(dev, ino, cp.offset)
                entries[f"{dev}:{ino}"] = {
                    "path": cp.path, "offset": durable,
                    "read_offset": cp.offset,
                    "dev": cp.dev, "inode": cp.inode,
                    "sig": cp.signature, "sig_size": cp.signature_size,
                    "update_time": cp.update_time,
                }
            data = {"version": 3, "check_point": entries}
        dirname = os.path.dirname(self.path) or "."
        os.makedirs(dirname, exist_ok=True)
        # unique tmp per dumper (concurrent dumps can't truncate each
        # other's file mid-write) + fsync before the atomic swap: a crash
        # right after dump() must find either the old or the new file,
        # never a torn one — this file is what recovery resumes from
        fd, tmp = tempfile.mkstemp(prefix=".checkpoint-", suffix=".tmp",
                                   dir=dirname)
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(data, f)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        self.last_dump = time.monotonic()

    def load(self) -> None:
        if not self.path or not os.path.exists(self.path):
            return
        try:
            with open(self.path) as f:
                data = json.load(f)
            if not isinstance(data, dict):
                raise ValueError("checkpoint root is not an object")
        except (OSError, ValueError) as e:
            self._quarantine(e)
            return
        version = data.get("version", 1)
        with self._lock:
            for key, d in data.get("check_point", {}).items():
                # v1 files keyed entries by path; the entry body always
                # carried dev/inode, so both versions key the same way here
                path = d.get("path", key if version == 1 else "")
                cp = ReaderCheckpoint(
                    path=path, offset=d.get("offset", 0), dev=d.get("dev", 0),
                    inode=d.get("inode", 0), signature=d.get("sig", ""),
                    signature_size=d.get("sig_size", 0),
                    update_time=d.get("update_time", 0.0))
                self._checkpoints[self._key(cp)] = cp

    def _quarantine(self, err: Exception) -> None:
        """Corrupt/torn checkpoint: preserve the evidence as `.bad` (the
        next dump recreates the real file), alarm, and count — a silent
        restart-from-zero with no trace is how loss hides."""
        from ...monitor.alarms import AlarmLevel, AlarmManager, AlarmType
        bad = self.path + ".bad"
        try:
            os.replace(self.path, bad)
        except OSError:
            bad = "<unlinkable>"
        self.quarantined_loads += 1
        # what is discarded here is a metadata file, not events — the
        # events re-read from offset 0 and re-enter the ledger normally
        AlarmManager.instance().send_alarm(  # loonglint: disable=unledgered-drop
            AlarmType.CHECKPOINT_FAIL,
            f"corrupt checkpoint file quarantined to {bad}: {err}",
            AlarmLevel.ERROR)

    def dump_periodically(self, interval: float = 5.0) -> None:
        if time.monotonic() - self.last_dump >= interval:
            # runs on the file server's own thread: a slow fsync here is
            # a pause of the reader, so it gets a span
            with trace.span("checkpoint.dump"):
                self.dump()

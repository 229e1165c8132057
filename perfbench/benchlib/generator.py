"""The load generator: appends the line source's stream to the tailed file.

One writer, one file, sequential lines.  A helper thread synthesises the
stream in blocks ahead of the writer (bounded), so a burst is written from
memory.  Every write is recorded: when it was due (open loop), when it was
done, its first line and its line count.  Lateness (done minus due) is the
generator's own fault and is reported, never hidden: latency is timed from
when a write was DUE.
"""

from __future__ import annotations

import os
import queue
import threading
import time

import numpy as np

BLOCK_LINES = 1024            # 512 KiB of 512-byte lines
AHEAD_BLOCKS = 192            # at most 96 MiB synthesised ahead


class Generator:
    def __init__(self, source, path: str, clock=time.monotonic):
        self.source = source
        self.path = path
        self.clock = clock
        self.line_bytes = source.line_bytes
        self.next_seq = 0
        self.due: list = []
        self.done: list = []
        self.first: list = []
        self.count: list = []
        self._fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        self._blocks: queue.Queue = queue.Queue(maxsize=AHEAD_BLOCKS)
        self._halt = threading.Event()
        self._cur = memoryview(b"")
        self._producer = threading.Thread(target=self._produce,
                                          name="perfbench-synth", daemon=True)
        self._producer.start()

    # -- the stream ----------------------------------------------------------

    def _produce(self) -> None:
        seq = 0
        while not self._halt.is_set():
            block = self.source.block(seq, BLOCK_LINES).tobytes()
            seq += BLOCK_LINES
            while not self._halt.is_set():
                try:
                    self._blocks.put(block, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def _take(self, n_lines: int) -> list:
        """The next ``n_lines`` lines of the stream as buffers to write."""
        want = n_lines * self.line_bytes
        parts = []
        while want:
            if not len(self._cur):
                self._cur = memoryview(self._blocks.get())
            part = self._cur[:want]
            self._cur = self._cur[len(part):]
            parts.append(part)
            want -= len(part)
        return parts

    def write(self, n_lines: int, due=None) -> None:
        for part in self._take(n_lines):
            while len(part):
                part = part[os.write(self._fd, part):]
        now = self.clock()
        self.due.append(now if due is None else due)
        self.done.append(now)
        self.first.append(self.next_seq)
        self.count.append(n_lines)
        self.next_seq += n_lines

    @property
    def offset(self) -> int:
        return self.next_seq * self.line_bytes

    # -- the two loops -------------------------------------------------------

    def run_open(self, due_rel, n_lines, t0: float) -> None:
        """Write the schedule: write i at ``t0 + due_rel[i]``, never earlier;
        a late writer writes at once and the lateness is on record."""
        clock = self.clock
        for rel, n in zip(due_rel.tolist(), n_lines.tolist()):
            due = t0 + rel
            wait = due - clock()
            if wait > 1e-4:
                time.sleep(wait - 5e-5)
            self.write(n, due)

    def run_closed(self, lead_bytes: int, write_lines: int, settled_bytes,
                   stop: threading.Event) -> None:
        """Keep the file ``lead_bytes`` ahead of ``settled_bytes()`` (what the
        sink has settled) until ``stop``."""
        while not stop.is_set():
            if self.offset - settled_bytes() < lead_bytes:
                self.write(write_lines)
            else:
                time.sleep(0.001)

    def close(self) -> None:
        self._halt.set()
        self._producer.join(timeout=5)
        os.close(self._fd)

    def writes(self) -> dict:
        return {"due": np.asarray(self.due, np.float64),
                "done": np.asarray(self.done, np.float64),
                "first": np.asarray(self.first, np.int64),
                "count": np.asarray(self.count, np.int64)}

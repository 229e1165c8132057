"""Log file reader: chunked reads, rollback to last complete line (or last
complete multiline RECORD), rotation tracking by (dev, inode) + signature.

Reference: core/file_server/reader/LogFileReader.cpp — ReadLog :964,
GetRawData/ReadUTF8 :1518,1647 (pread into an arena StringBuffer, align to
the last complete line and roll back the rest), multiline-aware rollback to
the last complete record :2128-2180, GenerateEventGroup :2726 (ONE
zero-copy RawEvent per chunk); signature-based rotation detection
(CheckFileSignature); DevInode tracking (common/DevInode.h).

Multiline rollback is the cheap way to carry state across read chunks: the
held-back partial record simply STAYS IN THE FILE (offset doesn't advance),
so the next read re-delivers it intact — no buffer copies, no processor
state. Only when a record cannot be held (chunk-sized record, flush
timeout) does the reader ship a broken record, marking the group so
split_multiline's carry can stitch it downstream (SURVEY.md §5.7).
"""

from __future__ import annotations

import os
import re
import time
import zlib
from dataclasses import dataclass, field
from typing import Optional, Tuple

import numpy as np

from ... import chaos, trace
from ...models import (ColumnarLogs, EventGroupMetaKey, PipelineEventGroup,
                       SourceBuffer, columnar_enabled)
from ...runner import ack_watermark

DEFAULT_CHUNK = 512 * 1024
SIGNATURE_SIZE = 1024
ML_FLUSH_TIMEOUT_S = 5.0

FP_READ = chaos.register_point("file_input.read")


@dataclass
class DevInode:
    dev: int = 0
    inode: int = 0

    def valid(self) -> bool:
        return self.inode != 0

    def __hash__(self) -> int:
        return hash((self.dev, self.inode))


def get_dev_inode(path: str) -> DevInode:
    try:
        st = os.stat(path)
        return DevInode(st.st_dev, st.st_ino)
    except OSError:
        return DevInode()


@dataclass
class ReaderCheckpoint:
    path: str = ""
    offset: int = 0
    dev: int = 0
    inode: int = 0
    signature: str = ""
    signature_size: int = 0
    update_time: float = field(default_factory=time.time)


class LogFileReader:
    def __init__(self, path: str, chunk_size: int = DEFAULT_CHUNK,
                 multiline_start: Optional[str] = None,
                 multiline_end: Optional[str] = None,
                 ml_flush_timeout: float = ML_FLUSH_TIMEOUT_S,
                 encoding: str = "utf8",
                 presplit_lines: bool = False):
        self.path = path
        # loongcolumn: assemble the group COLUMNAR at read time — line
        # spans over the chunk's arena, computed by the same
        # split_chunk_spans pass the inner split processor runs (which
        # then no-ops on the already-columnar group).  Off by default —
        # the bare reader keeps the reference one-RawEvent-per-chunk
        # contract; the file-pipeline wiring (FileServer / static input)
        # opts in because THERE the inner split is always the default
        # '\n' splitter.
        self.presplit_lines = presplit_lines
        # "gbk" transcodes chunks to UTF-8 on read (reference ReadGBK,
        # LogFileReader.cpp:1807), holding a trailing partial multibyte
        # character in the file like the newline rollback does
        self.encoding = (encoding or "utf8").lower()
        self.chunk_size = chunk_size
        self.offset = 0
        self.dev_inode = DevInode()
        self.signature = b""
        self._fd: Optional[int] = None
        self.last_read_time = 0.0
        # multiline-aware rollback (start- or end-pattern anchored)
        self._ml_start = (re.compile(multiline_start.encode("latin-1"))
                          if multiline_start else None)
        self._ml_end = (re.compile(multiline_end.encode("latin-1"))
                        if multiline_end else None)
        self._ml_flush_timeout = ml_flush_timeout
        self._ml_hold_since = 0.0   # first time the current tail was held
        self._ml_hold_size = -1     # file size at that moment
        self._prev_partial = False  # last shipped chunk broke mid-record
        self._last_consumed = 0     # rollback_last() state
        self._last_prev_partial = False

    # -- lifecycle ----------------------------------------------------------

    def open(self) -> bool:
        try:
            self._fd = os.open(self.path, os.O_RDONLY)
        except OSError:
            self._fd = None
            return False
        st = os.fstat(self._fd)
        self.dev_inode = DevInode(st.st_dev, st.st_ino)
        return True

    def close(self) -> None:
        if self._fd is not None:
            os.close(self._fd)
            self._fd = None

    @property
    def is_open(self) -> bool:
        return self._fd is not None

    # -- signature / rotation ----------------------------------------------

    def _read_signature(self) -> bytes:
        assert self._fd is not None
        return os.pread(self._fd, SIGNATURE_SIZE, 0)

    def check_signature(self) -> bool:
        """False ⇒ file was truncated/rotated in place: restart from 0."""
        if self._fd is None:
            return True
        if not self.signature:
            self.signature = self._read_signature()
            return True
        cur = os.pread(self._fd, len(self.signature), 0)
        if cur != self.signature:
            self.signature = self._read_signature()
            self.offset = 0
            # replaced content: any held multiline state belonged to the OLD
            # file — the first new chunk must not be marked as a continuation
            self._prev_partial = False
            self._ml_hold_size = -1
            return False
        if len(self.signature) < SIGNATURE_SIZE:
            # Prefix still matches but the file was first seen short: extend
            # the signature as the file grows, so copytruncate rotation of
            # files sharing a short common prefix is still detected.
            self.signature = self._read_signature()
        return True

    def restore(self, cp: ReaderCheckpoint) -> None:
        self.offset = cp.offset
        self.signature = bytes.fromhex(cp.signature) if cp.signature else b""

    def checkpoint(self) -> ReaderCheckpoint:
        return ReaderCheckpoint(
            path=self.path, offset=self.offset,
            dev=self.dev_inode.dev, inode=self.dev_inode.inode,
            signature=self.signature.hex(),
            signature_size=len(self.signature))

    # -- reading ------------------------------------------------------------

    def backlog(self) -> int:
        """Unread bytes (size - offset); 0 when unreadable or truncated."""
        if self._fd is None:
            return 0
        try:
            size = os.fstat(self._fd).st_size
        except OSError:
            return 0
        return max(0, size - self.offset)

    def has_more(self) -> bool:
        if self._fd is None:
            return False
        try:
            size = os.fstat(self._fd).st_size
        except OSError:
            return False
        # size < offset is TRUNCATION, not emptiness: read() must run so
        # the offset resets and the rewritten content ships — a file
        # copytruncate'd below the old offset would otherwise sit unread
        # until it regrew past it
        return size != self.offset

    def read(self, force_flush: bool = False
             ) -> Optional[PipelineEventGroup]:
        """`_read` under an ``input.file.read`` span when it shipped a
        group (pread + newline align + presplit; a poll that found nothing
        leaves none).  Tracing off: one global read."""
        tracer = trace.active_tracer()
        if tracer is None:
            return self._read(force_flush)
        t0 = time.perf_counter()
        cpu0 = time.thread_time()
        group = self._read(force_flush)
        if group is not None:
            cpu_s = time.thread_time() - cpu0
            tracer.record_timed(
                "input", "input.file.read", t0, time.perf_counter() - t0,
                {"path": self.path,
                 "offset": self.offset - self._last_consumed,
                 "nbytes": self._last_consumed, "rows": len(group)}, cpu_s)
        return group

    def _read(self, force_flush: bool = False
              ) -> Optional[PipelineEventGroup]:
        """One chunked read → event group with ONE RawEvent (zero-copy).

        Rolls back to the last '\\n' so only complete lines ship; if the
        chunk has no newline it ships whole only when force_flush or the
        chunk filled (oversized single line).
        """
        if self._fd is None and not self.open():
            return None
        if not self.check_signature():
            pass  # rotated in place: offset reset above, fall through
        fd = self._fd  # local copy: concurrent close() → EBADF, not TypeError
        if fd is None:
            return None
        try:
            # injected OSError = transient read failure (NFS hiccup,
            # rotated-away fd): this poll round yields nothing, the next
            # one re-reads from the unchanged offset — no bytes skipped
            chaos.faultpoint(FP_READ, exc=OSError)
            size = os.fstat(fd).st_size
        except OSError:
            return None
        if size < self.offset:       # truncated
            self.offset = 0
            self._prev_partial = False
            self._ml_hold_size = -1
        if (not force_flush and self._ml_hold_size == size
                and time.monotonic() - self._ml_hold_since
                < self._ml_flush_timeout):
            # still holding the same open record and nothing new arrived:
            # skip the pread + backward scan (the hold would re-run on the
            # same bytes every poll round otherwise)
            return None
        want = min(self.chunk_size, size - self.offset)
        if want <= 0:
            return None
        data = os.pread(fd, want, self.offset)
        if not data:
            return None
        filled = len(data) == self.chunk_size
        nl = data.rfind(b"\n")
        if nl >= 0:
            aligned = data[: nl + 1]      # roll back the partial tail line
        elif filled or force_flush:
            aligned = data                # oversized single line / final flush
        else:
            return None                   # wait for the line to complete

        # multiline-aware rollback: hold the trailing INCOMPLETE record in
        # the file (reference LogFileReader.cpp:2128-2180) so records never
        # split across chunks on the normal path
        partial_tail = False
        if (self._ml_start or self._ml_end) and not force_flush:
            ship = self._ml_align(aligned)
            if ship == 0 and filled:
                # a single record larger than a whole chunk: holding is
                # impossible, ship it broken and let the carry stitch it
                partial_tail = True
            elif ship < len(aligned):
                if filled:
                    # backlog catch-up: more bytes follow immediately; hold
                    # the open tail in the file (zero-copy carry), no clock
                    aligned = aligned[:ship]
                else:
                    now = time.monotonic()
                    if size != self._ml_hold_size:
                        # new bytes arrived since we started holding —
                        # restart the flush clock
                        self._ml_hold_size = size
                        self._ml_hold_since = now
                    if now - self._ml_hold_since >= self._ml_flush_timeout:
                        partial_tail = True   # flush the open record anyway
                    else:
                        aligned = aligned[:ship]
                        if not aligned:
                            return None
            else:
                self._ml_hold_size = -1
                if self._prev_partial and self._ml_end is None:
                    # start-mode chunk with no start line at all: these
                    # lines still continue the broken record — keep the
                    # stitch chain open for the carry downstream
                    partial_tail = True
        if partial_tail or force_flush:
            self._ml_hold_size = -1
        read_offset = self.offset
        src = aligned    # pre-transcode SOURCE bytes — what the crc covers
        if self.encoding == "gbk":
            aligned, consumed_src = self._transcode_gbk(aligned, force_flush)
            if not aligned:
                return None
        else:
            consumed_src = len(aligned)
        # crc of the consumed source span: loongcrash replay dedup verifies
        # re-read content identity, not just [offset, length) containment
        span_crc = zlib.crc32(src[:consumed_src])
        # snapshot for rollback_last(): a rejected queue push must restore
        # BOTH the offset and the multiline stitch state, or the re-read
        # chunk ships without its ML_CONTINUE marker
        self._last_consumed = consumed_src
        self._last_prev_partial = self._prev_partial
        self.offset += consumed_src
        self.last_read_time = time.monotonic()

        sb = SourceBuffer(capacity=len(aligned) + 256)
        view = sb.copy_string(aligned)
        group = PipelineEventGroup(sb)
        ts = int(time.time())
        if self.presplit_lines and columnar_enabled():
            # columnar group assembly (loongcolumn): the rows ARE line
            # spans over this chunk's arena from the moment the group
            # exists — the inner split processor no-ops downstream.
            # Shares split_chunk_spans with that processor, so the two
            # split implementations cannot diverge.  Gated on
            # columnar_enabled(): in dict mode the chunk must ship as a
            # RawEvent so the split/multiline chain runs its own course —
            # a presplit group would be materialized at the split
            # boundary and silently no-op the requires_columnar
            # multiline stage.
            from ...processor.split_log_string import split_chunk_spans
            offs, lens = split_chunk_spans(sb.as_array(), view.offset,
                                           view.length, ord("\n"))
            group.set_columns(ColumnarLogs(
                offsets=np.asarray(offs, dtype=np.int32),
                lengths=lens,
                timestamps=np.full(len(offs), ts, dtype=np.int64)))
        else:
            ev = group.add_raw_event(ts)
            ev.set_content(view)
        group.set_metadata(EventGroupMetaKey.LOG_FILE_PATH, self.path)
        group.set_metadata(EventGroupMetaKey.LOG_FILE_INODE,
                           str(self.dev_inode.inode))
        group.set_metadata(EventGroupMetaKey.LOG_FILE_DEV,
                           str(self.dev_inode.dev))
        group.set_metadata(EventGroupMetaKey.LOG_FILE_OFFSET, str(read_offset))
        # SOURCE bytes consumed (≠ content length under GBK transcode):
        # exactly-once ranges and back-pressure rollback index the raw file
        group.set_metadata(EventGroupMetaKey.LOG_FILE_LENGTH,
                           str(consumed_src))
        group.set_metadata(EventGroupMetaKey.LOG_FILE_CRC32, str(span_crc))
        # the span is now in flight: the acked-offset watermark owes it a
        # terminal ack before the checkpoint may advance past it
        ack_watermark.note_read(self.dev_inode.dev, self.dev_inode.inode,
                                read_offset, consumed_src, span_crc)
        # stitch markers for split_multiline's cross-group carry: this chunk
        # ends mid-record / continues the previous chunk's open record
        if partial_tail:
            group.set_metadata(EventGroupMetaKey.ML_PARTIAL_TAIL, "1")
        if self._prev_partial:
            group.set_metadata(EventGroupMetaKey.ML_CONTINUE, "1")
        self._prev_partial = partial_tail
        return group

    def rollback_last(self) -> None:
        """Undo the last read() (queue rejected the group): offset AND the
        multiline stitch chain return to their pre-read values."""
        self.offset -= getattr(self, "_last_consumed", 0)
        self._last_consumed = 0
        self._prev_partial = getattr(self, "_last_prev_partial",
                                     self._prev_partial)
        self._ml_hold_size = -1

    @staticmethod
    def _transcode_gbk(data: bytes, force_flush: bool
                       ) -> Tuple[bytes, int]:
        """GBK bytes → (utf-8 bytes, source bytes consumed).

        A partial multibyte character at the END stays in the file (next
        read completes it) unless force_flush; invalid bytes mid-stream
        are replaced (the reference tolerates mixed content rather than
        stalling the reader). Newline alignment upstream is GBK-safe:
        0x0A never appears as a trail byte — which also means a chunk
        ENDING at a newline cannot end mid-character, so only chunks cut
        elsewhere (filled mid-line) may hold bytes back.
        """
        can_hold = not force_flush and not data.endswith(b"\n")
        consumed = len(data)
        while True:
            try:
                text = data[:consumed].decode("gbk")
                break
            except UnicodeDecodeError as ue:
                if can_hold and ue.start >= consumed - 2 \
                        and ue.end >= consumed:
                    # dangling lead byte at the chunk end: hold it
                    consumed = ue.start
                    if consumed == 0:
                        return b"", 0
                    continue
                text = data[:consumed].decode("gbk", errors="replace")
                break
        return text.encode("utf-8"), consumed

    def _ml_align(self, data: bytes) -> int:
        """Bytes of `data` that form COMPLETE multiline records.

        End-pattern mode: a record closes at each end-matching line — ship
        through the last one. Start-pattern mode: the last start-matching
        line opens a still-growing record — ship everything before it.
        Scans backward so the common case (open record = a few tail lines)
        touches only those lines. Returns len(data) when nothing anchors
        (leading unmatched content ships and is handled downstream).
        """
        e = len(data)                 # exclusive end of the current line
        if self._ml_end is not None:
            while e > 0:
                s = data.rfind(b"\n", 0, e - 1) + 1
                line = data[s:e - 1] if data[e - 1:e] == b"\n" else data[s:e]
                if self._ml_end.fullmatch(line):
                    return e          # record closed here; tail is open
                e = s
            return 0                  # no closed record yet
        while e > 0:
            s = data.rfind(b"\n", 0, e - 1) + 1
            line = data[s:e - 1] if data[e - 1:e] == b"\n" else data[s:e]
            if self._ml_start.fullmatch(line):
                return s              # this start opens the (open) tail record
            e = s
        return len(data)

"""FileServer: the file-input singleton runner.

Reference: core/file_server/FileServer.cpp facade +
file_server/event_handler/LogInput.cpp:357 (ProcessLoop — the single event
thread driving discovery, modify events and reader reads, with CPU-adaptive
flow control :156-203) and BlockedEventManager (requeue on back-pressure).

One thread: each round it (1) runs discovery for every registered config on
its interval, (2) stats known files for modification, (3) drains readers of
changed files into the process queues, honouring watermark back-pressure —
a blocked read retries next round without losing the reader's offset.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Tuple

from ... import recovery, trace
from ...monitor.alarms import AlarmLevel, AlarmManager, AlarmType
from ...runner import ack_watermark
from ...utils import flags
from ...utils.logger import get_logger
from .checkpoint import CheckPointManager
from .event_listener import create_listener
from .polling import FileDiscoveryConfig, PollingDirFile
from .reader import LogFileReader

log = get_logger("file_server")


class FileInputStats:
    """The file server's always-on plain counters: integer adds per round
    or per chunk read, no lock.  One writer at a time — the event thread,
    and ``stop()`` only after it has joined that thread; readers
    (/debug/status, the metrics mirror) take whatever they find."""

    __slots__ = ("rounds_total", "rounds_throttled", "throttle_sleep_s",
                 "reads_total", "read_bytes_total", "reads_blocked_total",
                 "push_rejected_total")

    def __init__(self) -> None:
        self.rounds_total = 0
        self.rounds_throttled = {3: 0, 8: 0}   # by sleep stretch factor
        self.throttle_sleep_s = 0.0      # slept in stretched sleeps
        self.reads_total = 0             # chunk reads that shipped a group
        self.read_bytes_total = 0        # their SOURCE bytes
        self.reads_blocked_total = 0     # watermark high before the read
        self.push_rejected_total = 0     # rolled back after it

    def snapshot(self) -> dict:
        return {
            "rounds_total": self.rounds_total,
            "rounds_throttled_total": {str(f): n for f, n in
                                       self.rounds_throttled.items()},
            "throttle_sleep_seconds_total": round(self.throttle_sleep_s, 6),
            "reads_total": self.reads_total,
            "read_bytes_total": self.read_bytes_total,
            "reads_blocked_total": self.reads_blocked_total,
            "push_rejected_total": self.push_rejected_total,
        }


def status() -> Optional[dict]:
    """The /debug/status ``file_input`` section; None before a file
    server exists (observe-only: never constructs one)."""
    fs = FileServer._instance
    return fs.stats.snapshot() if fs is not None else None

DISCOVERY_INTERVAL_S = 1.0

# reference parity knobs (reader/LogFileReader.cpp:70 read_delay_alarm_duration,
# FileReaderOptions ReadDelayAlertThresholdBytes, EventHandler.cpp:342
# FILE_READER_EXCEED_ALARM reader-count ceiling)
flags.DEFINE_FLAG_INT64("read_delay_alarm_bytes",
                        "backlog bytes before READ_LOG_DELAY_ALARM",
                        200 * 1024 * 1024)
flags.DEFINE_FLAG_INT32("read_delay_alarm_duration",
                        "seconds between repeated read-delay alarms", 60)
flags.DEFINE_FLAG_INT32("max_file_reader_num",
                        "max simultaneously open log readers", 512)
flags.DEFINE_FLAG_INT32("checkpoint_dump_interval",
                        "checkpoint dump seconds", 5)
IDLE_SLEEP_S = 0.05
# with inotify the thread sleeps ON the fd, so the poll interval can relax:
# events wake it instantly and polling is only the discovery/rotation net
IDLE_SLEEP_INOTIFY_S = 0.25


class _ConfigState:
    def __init__(self, name: str, discovery: FileDiscoveryConfig,
                 queue_key: int, tail_existing: bool,
                 multiline_start: Optional[str] = None,
                 multiline_end: Optional[str] = None,
                 encoding: str = "utf8", chunk_size: Optional[int] = None):
        self.name = name
        self.poller = PollingDirFile(discovery)
        self.queue_key = queue_key
        self.readers: Dict[str, LogFileReader] = {}
        self.rotated: List[LogFileReader] = []  # old inodes still draining
        self.last_discovery = 0.0
        self.known: List[str] = []
        self.tail_existing = tail_existing
        self.first_round = True
        self.multiline_start = multiline_start
        self.multiline_end = multiline_end
        self.encoding = encoding
        self.chunk_size = chunk_size   # None = reader default (reference
                                       # ReadBufferSize config knob)
        self.pending: set = set()   # paths with bytes left after a drain
        # optional per-path group tags (container meta on stdio inputs):
        # callable(path) -> Dict[bytes, bytes] | None
        self.tag_provider = None

    def new_reader(self, path: str) -> LogFileReader:
        kwargs = {}
        if self.chunk_size:
            kwargs["chunk_size"] = self.chunk_size
        # presplit (loongcolumn): file-pipeline groups are columnar from
        # the read — the pipelines' inner split is always the default
        # '\n' splitter and no-ops downstream
        return LogFileReader(path, multiline_start=self.multiline_start,
                             multiline_end=self.multiline_end,
                             encoding=self.encoding, presplit_lines=True,
                             **kwargs)


class FileServer:
    _instance: Optional["FileServer"] = None
    _instance_lock = threading.Lock()

    def __init__(self) -> None:
        self._configs: Dict[str, _ConfigState] = {}
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._running = False
        self.process_queue_manager = None
        self.checkpoints = CheckPointManager()
        self._paused = False
        # CPU-adaptive flow control (reference LogInput::FlowControl,
        # event_handler/LogInput.cpp:156-203): 0..1 fraction of the agent's
        # CPU budget in use; high levels stretch the poll sleep
        self.cpu_level_provider = None
        # inotify merged with polling (EventListener_Linux.h); None on
        # non-Linux or when LOONG_DISABLE_INOTIFY is set
        self._listener = None
        self._dirty_paths: set = set()
        # False when any watch failed (max_user_watches, permission): the
        # poll interval stays tight so unwatched paths aren't slow-tailed
        self._watch_complete = False
        # BlockedEventManager analogue (reference event_handler/
        # BlockedEventManager.cpp + queue FeedbackInterface): a watermark-
        # rejected drain registers this server as the queue's feedback, so
        # the moment the runner pops the queue below its low watermark the
        # event thread wakes and resumes the blocked readers instead of
        # waiting out the poll sleep
        self._blocked_wake = threading.Event()
        self._feedback_keys: set = set()
        self.stats = FileInputStats()
        # path -> last alarm time (per-file alarm rate limiting)
        self._delay_alarms: Dict[str, float] = {}
        self._reader_limit_alarms: Dict[str, float] = {}

    @classmethod
    def instance(cls) -> "FileServer":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    # -- config registration (from InputFile plugins) -----------------------

    def add_config(self, name: str, discovery: FileDiscoveryConfig,
                   queue_key: int, tail_existing: bool = False,
                   multiline_start: Optional[str] = None,
                   multiline_end: Optional[str] = None,
                   tag_provider=None, encoding: str = "utf8",
                   chunk_size: Optional[int] = None) -> None:
        with self._lock:
            st = _ConfigState(
                name, discovery, queue_key, tail_existing,
                multiline_start=multiline_start, multiline_end=multiline_end,
                encoding=encoding, chunk_size=chunk_size)
            st.tag_provider = tag_provider
            self._configs[name] = st

    def update_config_paths(self, name: str, file_paths) -> None:
        """Replace a registered config's discovery globs (container churn);
        an empty list drains and prunes all current readers next round."""
        with self._lock:
            st = self._configs.get(name)
            if st is not None:
                st.poller.config.file_paths = list(file_paths)
                st.last_discovery = 0.0  # force rediscovery next round

    def remove_config(self, name: str) -> None:
        with self._lock:
            st = self._configs.pop(name, None)
        if st:
            for r in st.readers.values():
                self.checkpoints.update(r.checkpoint())
                r.close()

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        with self._lock:
            if self._running:
                return
            self._running = True
        self.checkpoints.load()
        self._listener = create_listener()
        self._thread = threading.Thread(target=self._run, name="file-server",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        with self._lock:
            if not self._running:
                return
            self._running = False
        if self._thread:
            self._thread.join(timeout=5)
            self._thread = None
        # final flush of partial lines + checkpoints
        with self._lock:
            states = list(self._configs.values())
        for st in states:
            for r in st.readers.values():
                self._drain_reader(st, r, force_flush=True)
                self.checkpoints.update(r.checkpoint())
                r.close()
        self.checkpoints.dump()

    def pause(self) -> None:
        self._paused = True

    def resume(self) -> None:
        self._paused = False

    # -- main loop ----------------------------------------------------------

    def _run(self) -> None:
        stats = self.stats      # this thread is its writer while it runs
        while self._running:
            if self._paused:
                time.sleep(IDLE_SLEEP_S)
                continue
            stats.rounds_total += 1
            try:
                busy = self._round()
                self.checkpoints.dump_periodically(
                    float(flags.get_flag("checkpoint_dump_interval")))
            except Exception:  # noqa: BLE001 - never kill the event thread
                log.exception("file server round failed")
                busy = False
            base = (IDLE_SLEEP_INOTIFY_S
                    if self._listener is not None and self._watch_complete
                    else IDLE_SLEEP_S)
            level = self.cpu_level_provider() if self.cpu_level_provider else 0.0
            # heavy throttle near the limit, a lighter one before it
            stretch = 8 if level > 0.9 else 3 if level > 0.7 else 0
            sleep = base * stretch if stretch else base
            if busy and level <= 0.9:
                continue
            if self._blocked_wake.is_set():
                # a queue we blocked on drained: resume immediately
                self._blocked_wake.clear()
                continue
            with self._lock:
                any_pending = any(st.pending for st in
                                  self._configs.values())
            if any_pending:
                # back-pressured readers outstanding: the inotify wait
                # below cannot see the feedback event, so bound the sleep
                # instead of waiting out the full (possibly throttled) tick
                sleep = min(sleep, 0.05)
            if stretch:
                # the governor stretched this sleep: counted, and timed as
                # slept (an inotify event or a feedback wakeup cuts it)
                stats.rounds_throttled[stretch] += 1
                t_sleep = time.monotonic()
            if self._listener is not None:
                # sleep ON the inotify fd: an append wakes the thread now,
                # not at the next poll tick (sub-poll-interval tail latency)
                for path, needs_discovery in self._listener.wait(sleep):
                    self._dirty_paths.add(path)
                    if needs_discovery:
                        with self._lock:
                            for st in self._configs.values():
                                st.last_discovery = 0.0
            else:
                self._blocked_wake.wait(sleep)
            if stretch:
                stats.throttle_sleep_s += time.monotonic() - t_sleep
        if self._listener is not None:
            self._listener.close()
            self._listener = None

    def _round(self) -> bool:
        """One round of the event thread.  Traced, a round that read a
        group is an ``input.file.round`` span, current for its body: the
        reads (``input.file.read``), each group's ``input.file.push`` and
        ``input.file.checkpoint`` and the round's ``input.file.discover``
        nest under it, and what is left is its self time (the stats of the
        files, the watermark gate, the loop).  A round that read nothing
        leaves no span, as a read that found nothing leaves none.  Tracing
        off: one global read."""
        tracer = trace.active_tracer()
        sp = (tracer.start_stage("input.file.round", "input.file.round")
              if tracer is not None else None)
        if sp is None:
            return self._round_body(None)
        reads0 = self.stats.reads_total
        discovered: list = []
        try:
            return self._round_body(discovered)
        finally:
            reads = self.stats.reads_total - reads0
            if reads:
                for name, t0, seconds, cpu_s in discovered:
                    tracer.record_timed(
                        "input.file.round", "input.file.discover", t0,
                        seconds, {"config": name}, cpu_s)
                sp.set_attr("reads", reads)
                sp.end()
            else:
                tracer.pop_current(sp)

    def _round_body(self, discovered: Optional[list]) -> bool:
        """The round itself.  ``discovered``: None, or the list a traced
        round's discovery passes go into — (config, start, seconds, CPU
        seconds) each, recorded by `_round` once it knows the round read
        something."""
        with self._lock:
            states = list(self._configs.values())
        dirty = self._dirty_paths
        self._dirty_paths = set()
        busy = False
        now = time.monotonic()
        live_dirs: set = set()
        for st in states:
            ran_discovery = False
            if now - st.last_discovery >= DISCOVERY_INTERVAL_S or st.first_round:
                ran_discovery = True
                st.last_discovery = now
                if discovered is not None:
                    t_disc = time.perf_counter()
                    c_disc = time.thread_time()
                st.known = st.poller.poll()
                for path in st.known:
                    if path not in st.readers:
                        self._open_reader(st, path)
                    else:
                        self._check_rotation(st, path)
                if discovered is not None:
                    cpu_s = time.thread_time() - c_disc
                    discovered.append((st.name, t_disc,
                                       time.perf_counter() - t_disc, cpu_s))
                # prune readers whose file left the glob or was deleted —
                # otherwise open fds pin deleted files' disk space forever
                known_set = set(st.known)
                for path in list(st.readers):
                    if path not in known_set:
                        r = st.readers.pop(path)
                        self._drain_reader(st, r, force_flush=True)
                        self.checkpoints.remove(r.dev_inode.dev,
                                                r.dev_inode.inode)
                        r.close()
                        self._delay_alarms.pop(path, None)
                        self._reader_limit_alarms.pop(path, None)
                st.first_round = False
            # drain readers with unread bytes. With complete inotify
            # coverage, off-discovery rounds only stat files that fired an
            # event or still had bytes after the last burst — THE idle-CPU
            # win of the listener; the periodic discovery pass remains the
            # safety net for inotify-silent filesystems.
            if self._listener is not None and self._watch_complete \
                    and not ran_discovery:
                targets = [st.readers[p]
                           for p in (dirty | st.pending) if p in st.readers]
            else:
                targets = list(st.readers.values())
            for r in targets:
                if ran_discovery:
                    # once per discovery pass is plenty for an alarm that
                    # rate-limits to one per minute; checking every poll
                    # tick would double the per-reader fstat load
                    self._check_read_delay(st, r)
                if r.has_more():
                    moved = self._drain_reader(st, r)
                    busy |= moved
                    if r.has_more():
                        st.pending.add(r.path)   # burst cap / back-pressure
                    else:
                        st.pending.discard(r.path)
                else:
                    st.pending.discard(r.path)
            for r in list(st.rotated):
                busy |= self._drain_reader(st, r, force_flush=True)
                if not r.has_more() and ack_watermark.fully_acked(
                        r.dev_inode.dev, r.dev_inode.inode):
                    # fully read AND every span terminally acked: only now
                    # may the inode's books close — dropping the checkpoint
                    # with spans still in flight would lose them on a crash.
                    # Remove only this reader's own inode entry — the live
                    # reader at the same path owns a different (dev, inode)
                    self.checkpoints.remove(r.dev_inode.dev,
                                            r.dev_inode.inode)
                    ack_watermark.tracker().forget(r.dev_inode.dev,
                                                   r.dev_inode.inode)
                    r.close()
                    st.rotated.remove(r)
            if self._listener is not None:
                import os as _os
                for path in st.known:
                    live_dirs.add(_os.path.dirname(path) or ".")
                for pattern in st.poller.config.file_paths:
                    # static prefix of each glob: catches files created later
                    d = _os.path.dirname(pattern)
                    while any(c in d for c in "*?["):
                        d = _os.path.dirname(d)
                    if d and _os.path.isdir(d):
                        live_dirs.add(d)
        if self._listener is not None:
            complete = True
            for d in live_dirs:
                complete = self._listener.watch_dir(d) and complete
            self._listener.unwatch_missing(live_dirs)
            self._watch_complete = complete
        return busy

    def _check_read_delay(self, st: _ConfigState, reader) -> None:
        """READ_LOG_DELAY_ALARM (reference LogFileReader.cpp:1540-1559):
        the writer is outrunning the reader by more than the threshold —
        alarm at most once per duration per file."""
        backlog = reader.backlog()
        if backlog <= flags.get_flag("read_delay_alarm_bytes"):
            self._delay_alarms.pop(reader.path, None)
            return
        now = time.monotonic()
        last = self._delay_alarms.get(reader.path, 0.0)
        if now - last < flags.get_flag("read_delay_alarm_duration"):
            return
        self._delay_alarms[reader.path] = now
        log.warning("read log delay: %s falls behind %d bytes",
                    reader.path, backlog)
        AlarmManager.instance().send_alarm(
            AlarmType.READ_LOG_DELAY,
            f"fall behind {backlog} bytes, path: {reader.path}",
            AlarmLevel.ERROR, st.name)

    def _register_feedback(self, queue_key: int) -> None:
        # registered on EVERY rejection (set_feedback replaces the list, so
        # this is idempotent): a deleted-and-recreated queue under the same
        # key gets the wakeup again; _feedback_keys is introspection only
        getter = getattr(self.process_queue_manager, "get_queue", None)
        q = getter(queue_key) if getter is not None else None
        if q is not None:
            q.set_feedback(self)
            self._feedback_keys.add(queue_key)

    def feedback(self, key: int) -> None:
        """Queue drained below its low watermark: wake the event thread so
        blocked readers resume immediately (FeedbackInterface)."""
        self._blocked_wake.set()

    def _check_rotation(self, st: _ConfigState, path: str) -> None:
        """rename+recreate rotation: the path's inode changed — finish the
        old inode via the rotated list, open a fresh reader at offset 0
        (reference: rotation via DevInode tracking, SURVEY.md §2.2)."""
        from .reader import get_dev_inode
        r = st.readers.get(path)
        if r is None:
            return
        cur = get_dev_inode(path)
        if cur.valid() and cur.inode != r.dev_inode.inode:
            st.rotated.append(r)
            # rotation churn must not blow past the fd ceiling: shed old
            # rotated readers first (best effort — the LIVE path always
            # reopens, or rotated data would be lost)
            self._shed_for_capacity(st, path)
            new = st.new_reader(path)
            if new.open():
                st.readers[path] = new
            else:
                del st.readers[path]

    def _reader_count(self) -> int:
        with self._lock:
            return sum(len(c.readers) + len(c.rotated)
                       for c in self._configs.values())

    def _shed_for_capacity(self, st: _ConfigState, path: str) -> bool:
        """At the reader ceiling: shed the oldest ROTATED reader (the
        reference cleans the rotator queue, EventHandler.cpp:330-348).
        Returns True when a slot was freed.  The alarm rate-limits per
        path — at a pinned limit a 1 s discovery pass would otherwise emit
        one alarm per pending file per second, forever."""
        if self._reader_count() < flags.get_flag("max_file_reader_num"):
            return True
        freed = False
        with self._lock:
            configs = list(self._configs.values())
        for c in configs:
            if c.rotated:
                old = c.rotated.pop(0)
                self.checkpoints.update(old.checkpoint())
                old.close()
                freed = True
                break
        now = time.monotonic()
        last = self._reader_limit_alarms.get(path, 0.0)
        if now - last >= flags.get_flag("read_delay_alarm_duration"):
            self._reader_limit_alarms[path] = now
            msg = (f"log reader count at limit "
                   f"({flags.get_flag('max_file_reader_num')}); "
                   + ("dropped an old rotated reader" if freed
                      else f"skipping {path}"))
            log.warning("%s", msg)
            AlarmManager.instance().send_alarm(
                AlarmType.FILE_READER_EXCEED, msg,
                AlarmLevel.WARNING, st.name)
        return freed

    def _open_reader(self, st: _ConfigState, path: str) -> None:
        if not self._shed_for_capacity(st, path):
            return
        r = st.new_reader(path)
        if not r.open():
            return
        cp = self.checkpoints.get(r.dev_inode.dev, r.dev_inode.inode)
        if cp is not None:
            r.restore(cp)
        elif not st.tail_existing and not st.first_round:
            pass  # new file appears later: read from 0
        elif not st.tail_existing and st.first_round:
            # skip history on first sight (reference TailExisted=false):
            import os
            try:
                r.offset = os.fstat(r._fd).st_size
            except OSError:
                pass
        # from here this source's checkpoint dumps use the ACKED frontier,
        # not the read offset (loongcrash at-least-once contract)
        ack_watermark.register_source(r.dev_inode.dev, r.dev_inode.inode,
                                      r.offset)
        st.readers[path] = r

    def _drain_reader(self, st: _ConfigState, reader: LogFileReader,
                      force_flush: bool = False) -> bool:
        """Read until empty or back-pressure; returns True if data moved."""
        moved = False
        pqm = self.process_queue_manager
        stats = self.stats      # one writer at a time: see FileInputStats
        for _ in range(64):  # bounded burst per round
            if pqm is not None and not pqm.is_valid_to_push(st.queue_key):
                # watermark high: requeue for the feedback wakeup
                stats.reads_blocked_total += 1
                self._register_feedback(st.queue_key)
                break
            try:
                group = reader.read(force_flush=force_flush)
            except OSError:
                break  # reader closed concurrently (config removal)
            if group is None or not reader.is_open:
                break
            stats.reads_total += 1
            stats.read_bytes_total += reader._last_consumed  # SOURCE bytes
            # the group's one read of the tracer: what the round does for
            # it beside the read is two spans, input.file.push (tags and
            # the queue) and input.file.checkpoint
            tracer = trace.active_tracer()
            if recovery.suppress_duplicate(group):
                # previous run already delivered this exact span (acked
                # after the last checkpoint dump): count it, advance the
                # books, and never let it re-enter the pipeline
                moved = True
                self._note_checkpoint(reader, tracer)
                continue
            if tracer is not None:
                t_push = time.perf_counter()
                c_push = time.thread_time()
            if st.tag_provider is not None:
                try:
                    tags = st.tag_provider(reader.path)
                except Exception:  # noqa: BLE001
                    tags = None
                if tags:
                    for k, v in tags.items():
                        group.set_tag(k, v)
            rejected = pqm is not None and \
                not pqm.push_queue(st.queue_key, group)
            if tracer is not None:
                cpu_s = time.thread_time() - c_push
                tracer.record_timed(
                    "input.file.round", "input.file.push", t_push,
                    time.perf_counter() - t_push, {"rejected": rejected},
                    cpu_s)
            if rejected:
                # queue rejected after read: restore offset (SOURCE
                # bytes) and the multiline stitch state together
                reader.rollback_last()
                stats.push_rejected_total += 1
                self._register_feedback(st.queue_key)
                break
            moved = True
            self._note_checkpoint(reader, tracer)
        return moved

    def _note_checkpoint(self, reader: LogFileReader, tracer) -> None:
        """The reader's offset into the checkpoint table, after a group of
        its was taken in (``input.file.checkpoint`` when traced)."""
        if tracer is None:
            self.checkpoints.update(reader.checkpoint())
            return
        t0 = time.perf_counter()
        c0 = time.thread_time()
        self.checkpoints.update(reader.checkpoint())
        cpu_s = time.thread_time() - c0
        tracer.record_timed("input.file.round", "input.file.checkpoint", t0,
                            time.perf_counter() - t0, None, cpu_s)

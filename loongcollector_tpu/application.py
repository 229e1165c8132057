"""Application: the agent process.

Reference: core/application/Application.cpp — Init (:96: identity, dirs,
app_info), Start (:222: monitors → config providers → runners sink-to-source
→ registry → 1 Hz supervision loop :313-398), Exit (:417: ordered stop with
a flush-out budget); core/logtail.cpp:154 (main: flags, signal handlers).

Run: python -m loongcollector_tpu --config <dir> [--once]
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time

from .config.common_provider import CommonConfigProvider
from .config.onetime import OnetimeConfigInfoManager
from .config.watcher import PipelineConfigWatcher
from .input.file.file_server import FileServer
from .monitor import startup
from .monitor.alarms import AlarmManager
from .monitor.metrics import WriteMetrics
from .monitor.watchdog import LoongCollectorMonitor
from .pipeline.batch.timeout_flush_manager import TimeoutFlushManager
from .pipeline.pipeline_manager import CollectionPipelineManager
from .pipeline.queue.process_queue_manager import ProcessQueueManager
from .pipeline.queue.sender_queue import SenderQueueManager
from .runner.disk_buffer import DiskBufferWriter
from .runner.flusher_runner import FlusherRunner
from .runner.http_sink import HttpSink
from .runner.processor_runner import ProcessorRunner
from .utils import flags
from .utils.crash_backtrace import (check_previous_crash,
                                    init_crash_backtrace, record_crash)
from .utils.logger import get_logger

log = get_logger("application")

# process_thread_count is defined by runner.processor_runner (loongshard
# default >1); app-config overrides still apply through the flag registry
flags.DEFINE_FLAG_INT32("config_scan_interval", "config rescan seconds", 10)
# checkpoint_dump_interval is defined by input.file.file_server (the dump
# cadence is the file server's knob); app-config overrides apply through
# the flag registry as usual
flags.DEFINE_FLAG_DOUBLE("exit_flush_timeout", "flush-out budget on exit (s)", 20.0)
flags.DEFINE_FLAG_STRING("config_server_address", "remote ConfigServer endpoint", "")
flags.DEFINE_FLAG_STRING("config_server_protocol",
                         "ConfigServer protocol: v2 (default) or v1", "v2")


class Application:
    def __init__(self, config_dir: str, data_dir: str = ""):
        self.config_dir = config_dir
        self.data_dir = data_dir or os.path.join(
            os.path.expanduser("~"), ".loongcollector_tpu")
        # app-level config overrides flags and must load BEFORE any
        # component reads them (thread counts, config server address...)
        self._load_app_config()
        self.process_queue_manager = ProcessQueueManager()
        self.sender_queue_manager = SenderQueueManager()
        self.pipeline_manager = CollectionPipelineManager(
            self.process_queue_manager, self.sender_queue_manager)
        self.http_sink = HttpSink()
        from .utils.payload_crypto import PayloadCipher
        try:
            spill_cipher = PayloadCipher(
                os.path.join(self.data_dir, "spill_key"))
        except (OSError, ValueError) as e:
            # a broken key file must not take the agent down — run with
            # plaintext spill and alarm loudly (existing encrypted files
            # are kept untouched until the key is restored)
            log.error("spill cipher unavailable (%s); disk buffer will "
                      "write PLAINTEXT", e)
            spill_cipher = None
        self.disk_buffer = DiskBufferWriter(
            os.path.join(self.data_dir, "buffer"),
            cipher=spill_cipher)
        from .flusher.async_sink import set_default_disk_buffer
        set_default_disk_buffer(self.disk_buffer)
        self.flusher_runner = FlusherRunner(self.sender_queue_manager,
                                            self.http_sink,
                                            disk_buffer=self.disk_buffer)
        # loongchaos: LOONG_CHAOS_SEED activates the deterministic fault
        # plane for this process (docs/robustness.md); no-op otherwise
        from . import chaos
        if chaos.install_from_env():
            log.warning("chaos plane ACTIVE (seed from %s)", chaos.ENV_SEED)
        # loongtrace: LOONG_TRACE=1 activates the span layer (sampling via
        # LOONG_TRACE_SAMPLE/LOONG_TRACE_SEED); LOONG_EXPO_PORT serves the
        # Prometheus-text endpoint (docs/observability.md)
        from . import trace
        if trace.install_from_env():
            log.info("loongtrace ACTIVE (sample=%s)",
                     trace.active_tracer().config.sample_rate)
        # loongprof: LOONG_PROF=1 starts the sampling profiler
        # (LOONG_PROF_HZ shapes the rate); the flight recorder is always
        # on and dumps on SIGTERM / watchdog breach / crash
        from . import prof
        if prof.install_from_env():
            log.info("loongprof ACTIVE (%.0f Hz)",
                     prof.active_profiler().hz)
        # loongledger: LOONG_LEDGER=1 turns on event-conservation
        # accounting; LOONG_LEDGER_AUDIT=1 additionally runs the
        # continuous zero-loss auditor (docs/observability.md)
        from .monitor import ledger
        if ledger.install_from_env():
            log.info("loongledger ACTIVE (audit=%s)",
                     ledger.auditor() is not None)
        # loongslo: LOONG_SLO=1 turns on the end-to-end freshness SLO
        # plane — ingest-stamped sojourn, burn-rate alerts, /debug/slo
        # (docs/observability.md)
        from .monitor import slo
        if slo.install_from_env():
            log.info("loongslo ACTIVE (evaluator=%s)",
                     slo.evaluator() is not None)
        # loongxprof: LOONG_XPROF=1 records the per-dispatch device
        # timeline (h2d/submit/exec/d2h legs, /debug/timeline); compile
        # and device-memory accounting are always on (docs/observability.md)
        from .ops import xprof
        if xprof.install_from_env():
            log.info("loongxprof ACTIVE")
        from .monitor.exposition import start_from_env as _expo_from_env
        self.exposition = _expo_from_env()
        from .runner.processor_runner import resolve_thread_count
        self.processor_runner = ProcessorRunner(
            self.process_queue_manager, self.pipeline_manager,
            thread_count=resolve_thread_count())
        self.config_watcher = PipelineConfigWatcher()
        from .config.instance_config import (InstanceConfigManager,
                                             InstanceConfigWatcher)
        self.instance_watcher = InstanceConfigWatcher()
        self.instance_manager = InstanceConfigManager.instance()
        self.remote_provider = None
        endpoint = flags.get_flag("config_server_address")
        if endpoint:
            proto = flags.get_flag("config_server_protocol").strip().lower()
            if proto == "v1":
                from .config.legacy_provider import LegacyConfigProvider
                provider_cls = LegacyConfigProvider
            else:
                if proto not in ("", "v2"):
                    log.error("unknown config_server_protocol %r; "
                              "falling back to v2", proto)
                provider_cls = CommonConfigProvider
            self.remote_provider = provider_cls(
                endpoint, os.path.join(self.data_dir, "remote_config"))
        self.watchdog = LoongCollectorMonitor(
            on_limit_breach=self._on_limit_breach)
        self._sig_stop = threading.Event()
        self._sig_received = None   # signum, set async-safely by the handler

    def _load_app_config(self) -> None:
        """Agent-level config file (reference loongcollector_config.json +
        AppConfig): a flat dict of flag overrides in the data or config
        dir."""
        for d in (self.data_dir, self.config_dir):
            path = os.path.join(d, "loongcollector_config.json")
            if not os.path.exists(path):
                continue
            try:
                with open(path) as f:
                    overrides = json.load(f)
            except (OSError, ValueError) as e:
                log.error("bad app config %s: %s", path, e)
                continue
            for k, v in overrides.items():
                if flags.has_flag(k):
                    flags.set_flag(k, v)
                    log.info("app config: %s = %r", k, v)
            return

    def init(self) -> None:
        os.makedirs(self.data_dir, exist_ok=True)
        check_previous_crash(self.data_dir)
        init_crash_backtrace(self.data_dir)
        # unsolicited flight dumps (signals, watchdog, crash) land next to
        # the crash backtrace so one directory holds the whole post-mortem
        from .prof import flight
        flight.set_dump_dir(self.data_dir)
        # loongcrash: detect unclean shutdown, load the acked-span journal
        # into the replay-duplicate window, sweep torn spill temps, and
        # start journaling this run's acks — BEFORE any reader opens (the
        # suppression window must be live when the first re-read arrives)
        from . import recovery
        recovery.begin(self.data_dir,
                       os.path.join(self.data_dir, "buffer"))
        # loongfuse: fused multi-pattern automata persist under
        # <data_dir>/dfa_cache/ — restarts and pipeline hot-reloads load
        # the compiled DFA by pattern-set content hash instead of paying
        # determinize+minimize again
        from .ops.regex import fuse
        fuse.set_cache_dir(self.data_dir)
        # loongresident: fused pipeline-program plan records persist under
        # <data_dir>/fused_cache/ — restarts skip plan construction and
        # recover the observed jit geometries for AOT warm
        from .ops import fused_pipeline
        fused_pipeline.set_cache_dir(self.data_dir)
        from .pipeline.plugin.checkpoint import (PluginCheckpointStore,
                                                 set_default_store)
        set_default_store(PluginCheckpointStore(
            os.path.join(self.data_dir, "plugin_checkpoints.json")))
        self.onetime_manager = OnetimeConfigInfoManager(
            os.path.join(self.data_dir, "onetime_state.json"))
        self.onetime_manager.load()
        self.pipeline_manager.onetime_manager = self.onetime_manager
        from .input.file.checkpoint_v2 import get_default_manager
        eo_mgr = get_default_manager(
            os.path.join(self.data_dir, "checkpoint_v2.db"))
        # snapshot uncommitted ranges NOW — before any pipeline starts and
        # new sends INSERT OR REPLACE over the same slot keys
        self._eo_pending = list(eo_mgr.uncommitted()) if eo_mgr else []
        # EO ranges subsume reader offsets: bump v1 checkpoints past every
        # uncommitted range BEFORE any reader opens, so the normal tail path
        # never re-reads bytes the EO replay will re-inject (that overlap
        # would double-deliver after a hard crash).
        if self._eo_pending:
            from .input.file.reader import ReaderCheckpoint, SIGNATURE_SIZE
            fs = FileServer.instance()
            fs.checkpoints.path = os.path.join(self.data_dir,
                                               "checkpoints.json")
            fs.checkpoints.load()
            bumped = False
            for cp in self._eo_pending:
                if not cp.file_path or cp.read_length <= 0:
                    continue
                end = cp.read_offset + cp.read_length
                # Find the live v1 entry. Legacy EO records carry dev=0 (the
                # reader only exported inode then), so (cp.dev, cp.inode) may
                # not be a real key — fall back to path lookup, and reject a
                # path hit whose inode disagrees (file was rotated since).
                v1 = None
                if cp.dev and cp.inode:
                    v1 = fs.checkpoints.get(cp.dev, cp.inode)
                if v1 is None:
                    v1 = fs.checkpoints.get_by_path(cp.file_path)
                    if v1 is not None and cp.inode and v1.inode != cp.inode:
                        v1 = None
                if v1 is None or v1.offset < end:
                    # bump IN PLACE: keep the found entry's real (dev, inode)
                    # key — keying by the EO record's possibly-zero dev would
                    # write a dead entry the reader never restores
                    dev, inode = ((v1.dev, v1.inode) if v1 is not None
                                  else (cp.dev, cp.inode))
                    try:
                        pst = os.stat(cp.file_path)
                    except OSError:
                        pst = None
                    if not inode or not dev:
                        if pst is None:
                            continue  # file gone: nothing to protect
                        dev, inode = pst.st_dev, pst.st_ino
                    sig = v1.signature if v1 is not None else ""
                    if not sig and pst is not None and \
                            (pst.st_dev, pst.st_ino) == (dev, inode):
                        # capture the head as the rotation signature — but
                        # only while the path still IS this (dev, inode);
                        # after rotation the path holds a different file
                        # whose head would poison the signature check
                        try:
                            with open(cp.file_path, "rb") as f:
                                sig = f.read(SIGNATURE_SIZE).hex()
                        except OSError:
                            sig = ""
                    fs.checkpoints.update(ReaderCheckpoint(
                        path=cp.file_path, offset=end,
                        dev=dev, inode=inode,
                        signature=sig, signature_size=len(sig) // 2))
                    bumped = True
            if bumped:
                fs.checkpoints.dump()
        # warm the native library (and its one-shot build) here so the first
        # data batch never stalls behind a compiler invocation
        from . import native as _native
        _native.get_lib()
        startup.mark("native_loaded")
        # declarative runner matrix (reference PluginRegistry.cpp:162-196):
        # every singleton input runner gets wired — and later stopped —
        # through the registry, so new runners need no Application edits
        from .runner.input_registry import (InputRunnerRegistry,
                                            register_builtin_runners)
        register_builtin_runners()
        InputRunnerRegistry.wire_all(self.process_queue_manager)
        fs = FileServer.instance()
        fs.checkpoints.path = os.path.join(self.data_dir, "checkpoints.json")
        fs.cpu_level_provider = lambda: self.watchdog.cpu_level
        self.config_watcher.add_source(self.config_dir)
        # instance configs: agent-level flag overrides applied live,
        # without pipeline restarts (instance_config/ beside the pipeline
        # dir; reference InstanceConfigWatcher.cpp)
        cfg_abs = os.path.abspath(self.config_dir)
        self.instance_watcher.add_source(
            os.path.join(os.path.dirname(cfg_abs), "instance_config"))
        self.instance_watcher.add_source(
            os.path.join(cfg_abs, "instance_config"))
        if self.remote_provider is not None:
            self.config_watcher.add_source(self.remote_provider.config_dir)
            self.remote_provider.start()

    def start(self, once: bool = False) -> None:
        # sink-to-source: network sink → flusher runner → processor runner →
        # config/pipelines (which start inputs)
        self.http_sink.init()
        self.flusher_runner.init()
        self.processor_runner.init()
        self.watchdog.start()
        log.info("runners started; watching %s", self.config_dir)
        scan_interval = flags.get_flag("config_scan_interval")
        last_scan = 0.0
        while not self._sig_stop.is_set():
            now = time.monotonic()
            if now - last_scan >= (0 if last_scan == 0 else scan_interval):
                last_scan = now
                diff = self.config_watcher.check_config_diff()
                if not diff.empty():
                    self.pipeline_manager.update_pipelines(diff)
                    startup.mark("pipelines_started")
                # a control-plane-faulted removal must complete even if
                # the config dir never changes again (loongtenant)
                self.pipeline_manager.retry_pending_removals()
                idiff = self.instance_watcher.check_config_diff()
                if not idiff.empty():
                    self.instance_manager.update(idiff)
                self.sender_queue_manager.gc_marked()
                WriteMetrics.instance().gc_deleted()
                self.disk_buffer.replay(self._resolve_buffered_flusher)
                from .pipeline.plugin.checkpoint import get_default_store
                get_default_store().flush()
                self.pipeline_manager.check_onetime_completion(
                    self.process_queue_manager, self.sender_queue_manager)
                if self._eo_pending:
                    self._replay_exactly_once()
            if once:
                # drain mode for one-shot runs: wait until queues idle
                time.sleep(1.0)
                if (self.process_queue_manager.all_empty()
                        and self.sender_queue_manager.all_empty()):
                    break
            else:
                self._sig_stop.wait(1.0)
        if self._sig_received is not None:
            # a signalled agent leaves its last seconds on disk: the
            # flight ring (alarms, injections, breaker flips, stalls) +
            # final stacks.  Runs HERE, on the main loop after the wait
            # returned — never inside the signal handler, where the ring
            # or logging lock may already be held by the interrupted frame
            signum = self._sig_received
            log.info("signal %d received", signum)
            from .prof import flight
            flight.record("signal", signum=signum)
            flight.dump(reason=f"signal_{signum}")
        self.exit()

    def exit(self) -> None:
        """Ordered source-to-sink shutdown (reference Application::Exit +
        CollectionPipeline::Stop :491-532): inputs stop first, the processor
        runner drains the process queues THROUGH the pipelines, and only then
        are batchers final-flushed and the send path drained."""
        log.info("exiting: stopping inputs and draining")
        if self.remote_provider is not None:
            self.remote_provider.stop()
        self.watchdog.stop()
        from .runner.input_registry import InputRunnerRegistry
        InputRunnerRegistry.stop_all()
        self.processor_runner.stop()          # drains process queues
        self.pipeline_manager.stop_all()      # flush batchers, stop flushers
        TimeoutFlushManager.instance().flush_timeout_batches()
        self.flusher_runner.stop(
            drain=True, timeout=flags.get_flag("exit_flush_timeout"))
        self.http_sink.stop()
        if getattr(self, "exposition", None) is not None:
            self.exposition.stop()
        from . import prof
        prof.disable()                        # stop sampler, retire records
        from .monitor import slo
        slo.stop_evaluator()                  # SLO burn-rate thread, if any
        from .pipeline.plugin.checkpoint import get_default_store
        get_default_store().flush()
        # final checkpoint dump AFTER the flusher drain: FileServer.stop
        # dumped before the send path quiesced, so the watermark on disk is
        # stale by every ack the drain just completed — without this dump a
        # clean restart would re-read (and have to dedup) the whole window
        fs = FileServer.instance()
        if fs.checkpoints.path:
            try:
                fs.checkpoints.dump()
            except OSError:
                log.exception("final checkpoint dump failed")
        # everything drained and dumped: compact the ack journal and drop
        # the crash marker — the next start is a clean start
        from . import recovery
        recovery.mark_clean_exit()
        log.info("exit complete")

    def _replay_exactly_once(self) -> None:
        """Re-read and re-inject file ranges whose send never committed
        (crash between serialize and ack), from the snapshot taken at init.
        Entries wait until their pipeline loads (remote configs arrive
        asynchronously) and survive full queues; deletes are sequence-
        conditioned so a fresh in-flight range reusing the key is never
        clobbered.  Groups are marked IS_REPLAY so downstream may dedupe."""
        from .input.file.checkpoint_v2 import get_default_manager
        from .models import EventGroupMetaKey, PipelineEventGroup, SourceBuffer
        mgr = get_default_manager()
        if mgr is None:
            self._eo_pending = []
            return
        for cp in list(self._eo_pending):
            if not cp.file_path or cp.read_length <= 0:
                mgr.delete_if_sequence(cp.key, cp.sequence_id)
                self._eo_pending.remove(cp)
                continue
            pipeline_name = cp.key.split(":", 1)[0]
            p = self.pipeline_manager.find_pipeline(pipeline_name)
            if p is None:
                continue  # pipeline may still be loading (remote config)
            try:
                fd = os.open(cp.file_path, os.O_RDONLY)
                st = os.fstat(fd)
                if cp.inode and st.st_ino != cp.inode:
                    os.close(fd)
                    mgr.delete_if_sequence(cp.key, cp.sequence_id)
                    self._eo_pending.remove(cp)  # rotated: unrecoverable
                    continue
                data = os.pread(fd, cp.read_length, cp.read_offset)
                os.close(fd)
            except OSError:
                mgr.delete_if_sequence(cp.key, cp.sequence_id)
                self._eo_pending.remove(cp)
                continue
            # the normal read path transcodes GBK→UTF-8; the replayed raw
            # range must match or exactly the replayed events ship mojibake
            for icfg in (getattr(p, "config", None) or {}).get("inputs", []):
                if icfg.get("Type") == "input_file" and \
                        str(icfg.get("FileEncoding", "utf8")).lower() == "gbk":
                    from .input.file.reader import LogFileReader
                    data, _ = LogFileReader._transcode_gbk(
                        data, force_flush=True)
                    break
            sb = SourceBuffer(len(data) + 256)
            view = sb.copy_string(data)
            group = PipelineEventGroup(sb)
            ev = group.add_raw_event(int(time.time()))
            ev.set_content(view)
            group.set_metadata(EventGroupMetaKey.LOG_FILE_PATH, cp.file_path)
            group.set_metadata(EventGroupMetaKey.LOG_FILE_INODE,
                               str(cp.inode))
            group.set_metadata(EventGroupMetaKey.LOG_FILE_OFFSET,
                               str(cp.read_offset))
            group.set_metadata(EventGroupMetaKey.LOG_FILE_LENGTH,
                               str(cp.read_length))
            group.set_metadata(EventGroupMetaKey.IS_REPLAY, "true")
            if not self.process_queue_manager.push_queue(
                    p.process_queue_key, group):
                continue  # queue full: retry next supervision round
            mgr.delete_if_sequence(cp.key, cp.sequence_id)
            self._eo_pending.remove(cp)
            log.info("exactly-once replay: %s [%d,+%d)", cp.file_path,
                     cp.read_offset, cp.read_length)

    def _resolve_buffered_flusher(self, identity: dict):
        """Find the live flusher matching a spilled payload's identity
        (plugin_id disambiguates same-type flushers in one pipeline)."""
        p = self.pipeline_manager.find_pipeline(identity.get("pipeline", ""))
        if p is None:
            return None
        want_id = identity.get("plugin_id", "")
        for f in p.flushers:
            if want_id and f.plugin_id == want_id:
                return f.plugin
        if not want_id:  # legacy buffers without plugin_id
            for f in p.flushers:
                if f.plugin.name == identity.get("flusher_type"):
                    return f.plugin
        return None

    def _on_limit_breach(self, reason: str) -> None:
        """Sustained resource breach: log critically and exit for the
        supervisor to restart (reference watchdog suicide-and-restart)."""
        log.critical("resource limit breached: %s — exiting for restart", reason)
        self._sig_stop.set()

    def handle_signal(self, signum, frame) -> None:  # noqa: ARG002
        # Python signal handlers run on the main thread between bytecodes:
        # taking ANY non-reentrant lock here (the flight ring's, logging's)
        # can deadlock against the interrupted frame.  Only async-safe
        # work happens here — the flight dump runs from the main loop
        # right after the wait returns (see start()).
        self._sig_received = signum
        self._sig_stop.set()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="loongcollector_tpu")
    parser.add_argument("--config", required=True,
                        help="pipeline config directory")
    parser.add_argument("--data-dir", default="",
                        help="checkpoint/state directory")
    parser.add_argument("--once", action="store_true",
                        help="process available data then exit")
    parser.add_argument("--cpu", action="store_true",
                        help="run on the CPU backend on purpose (tests, "
                             "CPU drives); without it a missing "
                             "accelerator is fatal")
    args = parser.parse_args(argv)
    startup.mark("imports_done")

    # Bring the backend up BEFORE anything compiles: place the persistent
    # compile cache, say once where this process computes, and refuse to
    # carry on on a CPU nobody asked for (ops/device_info.py).
    from .ops import device_info
    try:
        info = device_info.start(force_cpu=args.cpu)
    except device_info.NoAcceleratorError as e:
        log.critical("%s", e)
        return 2
    startup.mark("backend_up")
    log.info("device backend: platform=%s device_kind=%s device_count=%d "
             "jax=%s jaxlib=%s libtpu=%s compile_cache=%s "
             "runtime_rss_mb=%d (outside memory_usage_limit_mb)",
             info["platform"], info["device_kind"], info["device_count"],
             info["jax"], info["jaxlib"], info["libtpu"],
             info["compile_cache_dir"], info["runtime_rss_bytes"] >> 20)

    app = Application(args.config, args.data_dir)
    signal.signal(signal.SIGTERM, app.handle_signal)
    signal.signal(signal.SIGINT, app.handle_signal)
    app.init()
    try:
        app.start(once=args.once)
    except Exception:  # noqa: BLE001 - persist the trace for restart report
        import traceback
        trace = traceback.format_exc()
        log.critical("unhandled exception in main loop:\n%s", trace)
        record_crash(app.data_dir, trace)
        from .prof import flight
        flight.record("crash", error=trace.strip().rsplit("\n", 1)[-1][:200])
        flight.dump(reason="crash")
        try:
            # the orderly drain is still possible — flush what we can before
            # the supervisor restarts us
            app.exit()
        except Exception:  # noqa: BLE001
            log.exception("drain after crash failed")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

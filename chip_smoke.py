#!/usr/bin/env python3
"""chip_smoke.py — the file→regex→sink deployment, once, on the chip.

The quickest proof that the system still starts on the accelerator.  It
drives the deployment the repo leads with (BASELINE.json config 1 /
example_config/quick_start/file_regex_apache.yaml, at upstream's
regression shape of 512-byte lines) through the entry point a user calls:

    input_file (tailing a real file)
      → processor_parse_regex_tpu (the Apache pattern, 9 captures)
      → processor_parse_timestamp_native
      → flusher_file
    run by  python -m loongcollector_tpu --config <dir> --data-dir <dir>

What it does, in order:

 1. rebuilds native/*.so from the committed sources on this machine;
 2. writes window 0 (a backlog) of seeded 512-byte Apache lines, ~1 % of
    them lines the pattern rejects, and starts the agent as a CHILD — this
    parent never imports jax, so exactly one process holds the chip;
 3. appends the other windows while the agent runs, waiting for each to
    reach the sink, then waits for the conservation ledger to quiesce;
 4. reads /debug/status and /debug/ledger from the agent (the process that
    holds the chip): platform, device_kind, device count, versions, which
    jit families compiled at which geometries and for how long, rows per
    routing tier, counted kernel fallbacks;
 5. stops the agent, then checks the sink against Python ``re`` on the
    same bytes: every line once, in order, all nine fields equal, rejected
    lines kept whole under ``rawLog``.

It fails — non-zero exit, the reasons as its last lines, no result line —
if the platform is not ``tpu``, if any phase fails, if the sink is wrong,
if the ledger residual is not 0, if fewer than 90 % of rows went through
the device, if a kernel fallback or lane respill was counted, or if the
agent's log holds a traceback, a critical line or a breach of its memory
limit.  It prints facts, not metrics.  On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import json
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

APACHE_RE = (r'(\S+) (\S+) (\S+) \[([^\]]+)\] '
             r'"(\S+) (\S+) ([^"]*)" (\d{3}) (\d+)')
KEYS = ["ip", "ident", "user", "time", "method", "url", "protocol",
        "status", "size"]
TIME_FORMAT = "%d/%b/%Y:%H:%M:%S %z"
LINE_BYTES = 512                  # upstream's regression shape, newline included
WINDOW_LINES = 65536              # 32 MiB per window
WINDOWS = 3                       # one backlog, two appended while it runs
PIPELINE = "chip_smoke"
MIN_DEVICE_SHARE = 0.90
#: the jit families a Tier-1 regex dispatch can be served by
EXTRACT_FAMILIES = ("extract", "extract_pallas", "sharded_parse")
#: whole-run budget, under the 1200 s the contract allows
DEADLINE_S = 1080.0


class SmokeFailure(Exception):
    """A phase failed; the message is the reason printed last."""


class HostRouted(SmokeFailure):
    """Default routing kept the groups on the host walker (nothing else
    was wrong): the measured crossover is above the group size here."""


# ---------------------------------------------------------------------------
# data: seeded 512-byte Apache lines (short fields, the size capture
# padded with digits to the width)

_METHODS = ("GET", "POST", "PUT", "HEAD")
_STATUS = ("200", "201", "301", "304", "404", "500")


def make_line(seed: int, i: int) -> bytes:
    """Line ``i`` of the stream for ``seed``: LINE_BYTES bytes, newline
    included.  The url carries ``i``, so every line is unique and the sink
    can be held to exactly-once, in order.  About 1 % of lines are ones the
    pattern rejects (three kinds), at the same width."""
    r = random.Random(seed * 1_000_003 + i)
    base = (f"10.{r.randrange(256)}.{(i >> 8) & 255}.{i & 255} - "
            f"user{r.randrange(997)} "
            f"[10/Oct/2000:13:{r.randrange(60):02d}:{i % 60:02d} -0700] "
            f'"{r.choice(_METHODS)} /api/v1/resource/{i} HTTP/1.1" '
            f"{r.choice(_STATUS)} ")
    reject = r.random() < 0.01
    if reject:
        kind = r.randrange(3)
        if kind == 0:                       # no opening bracket
            base = base.replace("[", "(", 1)
        elif kind == 1:                     # two-digit status
            base = base[:-2] + " "
    pad = LINE_BYTES - 1 - len(base)
    size = "1" + "0" * (pad - 1)
    if reject and kind == 2:                # a non-digit ends the size
        size = size[:-1] + "x"
    return (base + size).encode("ascii") + b"\n"


def make_window(seed: int, start: int, n: int) -> bytes:
    return b"".join(make_line(seed, i) for i in range(start, start + n))


# ---------------------------------------------------------------------------
# the reference: Python `re` on the same bytes, in the parent

_RX = re.compile(APACHE_RE.encode("latin-1"))
_time_cache: dict = {}


def _epoch(stamp: str) -> int:
    """``__time__`` as the deployment defines it: the stamp read in the
    machine's local time (no SourceTimezone is configured; like upstream,
    ``%z`` is matched but not applied)."""
    t = _time_cache.get(stamp)
    if t is None:
        t = int(time.mktime(time.strptime(stamp, TIME_FORMAT)))
        _time_cache[stamp] = t
    return t


def expected_record(line: bytes):
    """What the deployment must emit for one input line (newline
    stripped): the nine captures as strings plus the parsed ``__time__``
    when the pattern matches, else the whole line under ``rawLog`` (the
    processor's KeepingSourceWhenParseFail default) with ``__time__`` left
    to the read clock (returned as None)."""
    m = _RX.fullmatch(line)
    if m is None:
        return {"rawLog": line.decode("latin-1")}, None
    rec = {k: g.decode("latin-1") for k, g in zip(KEYS, m.groups())}
    return rec, _epoch(rec["time"])


def check_sink(sink_path: str, log_path: str) -> dict:
    """Hold the sink to the reference: line j of the sink is the record of
    line j of the input — so every line arrived exactly once, in order —
    with all nine fields equal.  Raises SmokeFailure at the first
    difference; returns row counts."""
    rows = matched = rejected = 0
    with open(log_path, "rb") as src, open(sink_path, "rb") as sink:
        for line in src:
            out = sink.readline()
            if not out:
                raise SmokeFailure(
                    f"sink ends after {rows} rows; input line {rows} "
                    f"never arrived")
            got = json.loads(out)
            want, want_time = expected_record(line.rstrip(b"\n"))
            got_time = got.pop("__time__", None)
            if got != want:
                raise SmokeFailure(
                    f"sink row {rows} differs from the re reference "
                    f"(lost, duplicated, reordered or misparsed):\n"
                    f"  want {_clip(want)}\n  got  {_clip(got)}")
            if want_time is None:
                rejected += 1
            else:
                matched += 1
                if got_time != want_time:
                    raise SmokeFailure(
                        f"sink row {rows}: __time__ {got_time} != "
                        f"{want_time} parsed from {want['time']!r}")
            rows += 1
        extra = sink.readline()
        if extra:
            raise SmokeFailure(
                f"sink holds rows past the {rows} input lines "
                f"(duplicate delivery): {_clip(extra)}")
    return {"rows": rows, "matched": matched, "rejected": rejected}


def _clip(obj, width: int = 300) -> str:
    s = obj.decode("latin-1") if isinstance(obj, bytes) else json.dumps(obj)
    return s if len(s) <= width else s[:width] + f"... ({len(s)} chars)"


# ---------------------------------------------------------------------------
# what the agent says about itself


def require_platform(dev: dict, platform: str) -> None:
    if dev.get("platform") != platform:
        raise SmokeFailure(
            f"platform is {dev.get('platform')!r}, not {platform!r} "
            f"(device_kind {dev.get('device_kind')!r}): no accelerator")


def judge_status(status: dict, ledger: dict, sink_rows: int,
                 platform: str = "tpu", one_chip: bool = True) -> dict:
    """Decide from /debug/status and /debug/ledger where the work ran.
    Raises SmokeFailure when it did not run where it should have; returns
    the facts to print."""
    dev = status.get("device")
    if not dev or "platform" not in dev:
        raise SmokeFailure("/debug/status has no device.platform: the "
                           "agent did not say where it computes")
    require_platform(dev, platform)
    if one_chip and "mesh" in status:
        raise SmokeFailure(
            "the one-chip run took a multi-chip path: /debug/status has a "
            f"mesh section {_clip(status['mesh'])}")

    routing = dev.get("routing") or {}
    rows = routing.get("rows") or {}
    if routing.get("kernel_fallbacks_total", 0):
        raise SmokeFailure(
            f"{routing['kernel_fallbacks_total']} device-kernel fallback(s) "
            f"counted: the kernel the engine chose first "
            f"({routing.get('kernel_first_choice')}) did not serve every "
            f"dispatch")
    # lanes exist only on a multi-chip path (a one-chip run has no mesh)
    respilled = sum(lane.get("respilled_events", 0) for lane in
                    (status.get("mesh") or {}).get("lanes") or [])
    if respilled:
        raise SmokeFailure(f"{respilled} rows respilled from a chip lane "
                           f"to the host")

    # rows that went to the device are the batch ring's real rows
    device_rows = ((status.get("streaming") or {}).get("ring") or {}
                   ).get("real_rows", 0)
    share = device_rows / sink_rows if sink_rows else 0.0
    if share < MIN_DEVICE_SHARE:
        raise (SmokeFailure if routing.get("forced") else HostRouted)(
            f"only {device_rows} of {sink_rows} rows ({share:.1%}) went "
            f"through the device; host walker {rows.get('host_walker', 0)}, "
            f"per-row re {rows.get('cpu_re', 0)}; probe "
            f"{routing.get('probe')}")

    compiled = {f: doc for f, doc in (status.get("compile") or {}).items()
                if f in EXTRACT_FAMILIES}
    first = routing.get("kernel_first_choice")
    if sorted(compiled) != [first]:
        raise SmokeFailure(
            f"kernel families {sorted(compiled)} served the rows but the "
            f"engine chose {first!r} first: a fallback happened")

    if not ledger.get("enabled"):
        raise SmokeFailure("the conservation ledger is off")
    prow = (ledger.get("pipelines") or {}).get(PIPELINE)
    if prow is None:
        raise SmokeFailure(f"ledger has no pipeline {PIPELINE!r}")
    sent = prow["boundaries"].get("send_ok", {}).get("events", 0)
    if prow["residual"] != 0 or ledger.get("inflight_live", 0) != 0 \
            or sent != sink_rows:
        raise SmokeFailure(
            f"ledger not conserved at quiesce: residual "
            f"{prow['residual']}, inflight_live "
            f"{ledger.get('inflight_live')}, send_ok {sent} of "
            f"{sink_rows} rows")
    alarms = (ledger.get("auditor") or {}).get("residual_alarms_total", 0)
    if alarms:
        raise SmokeFailure(f"ledger auditor raised {alarms} residual "
                           f"alarm(s)")

    fam = compiled[first]
    return {
        "device": {k: dev.get(k) for k in (
            "platform", "device_kind", "device_count", "jax", "jaxlib",
            "libtpu", "compile_cache_dir", "runtime_rss_bytes")},
        "kernel_family": first,
        "compile_seconds": round(fam["compile_ms_total"] / 1e3, 3),
        "compiles": fam["compiles"],
        "compile_geometries": {g: round(row["last_ms"] / 1e3, 3)
                               for g, row in fam["geometries"].items()},
        "other_compiled_families": sorted(
            f for f in (status.get("compile") or {}) if f != first),
        "dispatched_total": dev.get("dispatched_total"),
        "ring_geometries": {
            g: {"packs": row["packs"], "real_rows": row["real_rows"],
                "padded_rows": row["padded_rows"]}
            for g, row in ((status.get("streaming") or {})
                           .get("geometries") or {}).items()},
        "rows": {"sink": sink_rows, "device": device_rows, **rows,
                 "lane_respill": respilled},
        "device_share": round(share, 4),
        "routing_forced": routing.get("forced") or [],
        "routing_probe": routing.get("probe"),
        "mesh": status.get("mesh"),
        "workers": (status.get("workers") or {}).get("count"),
        "ledger_residual": prow["residual"],
    }


# ---------------------------------------------------------------------------
# driving the agent


def build_native() -> float:
    """Rebuild both native libraries from the committed sources on THIS
    machine (the Makefile compiles with -march=native, so a library built
    elsewhere may not even run here).  Raises when the build fails."""
    t0 = time.monotonic()
    native = os.path.join(ROOT, "native")
    for target in ("clean", "all"):
        r = subprocess.run(["make", "-C", native, "-s", target],
                           capture_output=True, text=True, timeout=300)
        if r.returncode != 0:
            raise SmokeFailure(f"make -C native {target} failed "
                               f"(rc {r.returncode}):\n{r.stderr[-3000:]}")
    for so in ("libloongcollector_native.so", "libloong_ebpf_sim.so"):
        if not os.path.exists(os.path.join(native, so)):
            raise SmokeFailure(f"native build left no {so}")
    return time.monotonic() - t0


def write_config(cfg_dir: str, log_path: str, sink_path: str) -> None:
    os.makedirs(cfg_dir)
    with open(os.path.join(cfg_dir, f"{PIPELINE}.yaml"), "w") as f:
        f.write(
            "inputs:\n"
            "  - Type: input_file\n"
            "    FilePaths:\n"
            f"      - {log_path}\n"
            "    TailingAllMatchedFiles: true\n"
            "processors:\n"
            "  - Type: processor_parse_regex_tpu\n"
            "    SourceKey: content\n"
            f"    Regex: '{APACHE_RE}'\n"
            f"    Keys: [{', '.join(KEYS)}]\n"
            "  - Type: processor_parse_timestamp_native\n"
            "    SourceKey: time\n"
            f"    SourceFormat: '{TIME_FORMAT}'\n"
            "flushers:\n"
            "  - Type: flusher_file\n"
            f"    FilePath: {sink_path}\n")


def agent_env(routing: str, one_chip: bool) -> dict:
    """The agent's environment: the caller's, plus the debug endpoint and
    the conservation ledger.  ``one_chip`` turns off both multi-chip modes
    by their existing switches, so the run uses one chip by construction
    on a host that has several; ``routing='forced'`` pins Tier-1 batches
    to the device tier with the existing LOONG_NATIVE_T1=0."""
    env = dict(os.environ)
    env["LOONG_EXPO_PORT"] = "0"
    env["LOONG_LEDGER_AUDIT"] = "1"
    env["PYTHONUNBUFFERED"] = "1"
    if one_chip:
        env["LOONG_SHARDED"] = "0"
        env["LOONG_MESH_LANES"] = "0"
    if routing == "forced":
        env["LOONG_NATIVE_T1"] = "0"
    return env


class Agent:
    """`python -m loongcollector_tpu` as a child, and its debug endpoint."""

    def __init__(self, cfg_dir: str, data_dir: str, log_file: str,
                 env: dict, deadline: float):
        self.log_file = log_file
        self.deadline = deadline
        self._log = open(log_file, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "loongcollector_tpu",
             "--config", cfg_dir, "--data-dir", data_dir],
            cwd=ROOT, env=env, stdout=self._log, stderr=subprocess.STDOUT)
        self.port = None

    def log_text(self) -> str:
        with open(self.log_file, "r", errors="replace") as f:
            return f.read()

    def check_alive(self, doing: str) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise SmokeFailure(f"the agent exited with code {rc} while "
                               f"{doing}")
        if time.monotonic() > self.deadline:
            raise SmokeFailure(f"out of time while {doing}")

    def wait_endpoint(self) -> None:
        pat = re.compile(r"exposition endpoint on http://127\.0\.0\.1:(\d+)/")
        while self.port is None:
            self.check_alive("starting (no debug endpoint yet)")
            m = pat.search(self.log_text())
            if m:
                self.port = int(m.group(1))
            else:
                time.sleep(0.2)

    def get(self, path: str) -> dict:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{self.port}{path}", timeout=30) as r:
            return json.loads(r.read())

    def wait_backend(self) -> dict:
        """Block until the agent has named its backend in /debug/status."""
        while True:
            self.check_alive("bringing its backend up")
            dev = self.get("/debug/status").get("device") or {}
            if "platform" in dev:
                return dev
            time.sleep(0.2)

    def stop(self) -> int:
        """SIGTERM, wait for the orderly drain; SIGKILL if it will not go.
        Returns the exit code."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        return self.proc.returncode


class SinkCounter:
    """Counts complete rows in the growing sink without re-reading it."""

    def __init__(self, path: str):
        self.path = path
        self.offset = 0
        self.rows = 0

    def poll(self) -> int:
        if os.path.exists(self.path):
            with open(self.path, "rb") as f:
                f.seek(self.offset)
                while True:
                    buf = f.read(1 << 22)
                    if not buf:
                        break
                    self.rows += buf.count(b"\n")
                    self.offset += len(buf)
        return self.rows


def wait_quiesced(agent: Agent, want_rows: int) -> dict:
    """Poll /debug/ledger until the pipeline is conserved and still."""
    while True:
        agent.check_alive("waiting for the ledger to quiesce")
        doc = agent.get("/debug/ledger")
        prow = (doc.get("pipelines") or {}).get(PIPELINE) or {}
        sent = (prow.get("boundaries") or {}).get("send_ok", {}) \
            .get("events", 0)
        if sent >= want_rows and prow.get("residual") == 0 \
                and doc.get("inflight_live") == 0:
            return doc
        time.sleep(0.25)


def check_agent_log(text: str) -> None:
    """The agent's own complaints fail the run: a traceback, a critical
    line, or a breach of its memory limit — ten of those in a row and the
    agent exits "for restart", which a drive shorter than ten seconds
    would never see."""
    lines = text.splitlines()
    for i, ln in enumerate(lines):
        if ("Traceback (most recent call last)" in ln or "[CRITICAL]" in ln
                or "watchdog: rss" in ln):
            raise SmokeFailure("the agent complained in its log:\n"
                               + "\n".join(lines[i:i + 12]))


def run(seed: int, windows: int, window_lines: int, routing: str,
        one_chip: bool, platform: str, work: str,
        deadline: float = None) -> dict:
    """Drive the deployment once; returns the facts.  Raises SmokeFailure
    (or whatever a phase raised) on any failure; always stops the agent.
    The command line always drives WINDOWS x WINDOW_LINES on one chip;
    the size, platform and ``one_chip`` arguments are for the tier-1 test
    (tiny, CPU pin) and for a by-hand look at a multi-chip host."""
    if deadline is None:
        deadline = time.monotonic() + DEADLINE_S
    log_path = os.path.join(work, "access.log")
    sink_path = os.path.join(work, "sink.jsonl")
    write_config(os.path.join(work, "config"), log_path, sink_path)
    with open(log_path, "wb") as f:
        f.write(make_window(seed, 0, window_lines))

    agent = Agent(os.path.join(work, "config"), os.path.join(work, "data"),
                  os.path.join(work, "agent.log"),
                  agent_env(routing, one_chip), deadline)
    facts: dict = {}
    try:
        t_start = time.monotonic()
        agent.wait_endpoint()
        dev = agent.wait_backend()
        facts["agent_start_seconds"] = round(time.monotonic() - t_start, 2)
        require_platform(dev, platform)   # before driving anything

        sink = SinkCounter(sink_path)
        window_seconds = []
        for w in range(windows):
            t0 = time.monotonic()
            if w:
                with open(log_path, "ab") as f:
                    f.write(make_window(seed, w * window_lines,
                                        window_lines))
            want = (w + 1) * window_lines
            while sink.poll() < want:
                agent.check_alive(f"waiting for window {w} "
                                  f"({sink.rows} of {want} rows in the sink)")
                time.sleep(0.1)
            window_seconds.append(round(time.monotonic() - t0, 2))
        total = windows * window_lines
        ledger = wait_quiesced(agent, total)
        status = agent.get("/debug/status")
        if sink.poll() != total:
            raise SmokeFailure(f"sink holds {sink.rows} rows, "
                               f"{total} were written")
        facts.update(judge_status(status, ledger, total, platform=platform,
                                  one_chip=one_chip))
        facts["window_seconds"] = window_seconds
        facts["windows"] = [windows, window_lines * LINE_BYTES]
    except BaseException:
        agent.stop()
        tail = agent.log_text().splitlines()[-40:]
        print("---- agent log (last lines) ----", flush=True)
        print("\n".join(tail), flush=True)
        raise
    rc = agent.stop()
    if rc != 0:
        raise SmokeFailure(f"the agent exited with code {rc} on SIGTERM")
    facts["sink"] = check_sink(sink_path, log_path)
    check_agent_log(agent.log_text())
    return facts


def report(facts: dict) -> None:
    d = facts["device"]
    print(f"device: platform={d['platform']} device_kind={d['device_kind']} "
          f"device_count={d['device_count']} (as the agent's JAX reports "
          f"the host) jax={d['jax']} jaxlib={d['jaxlib']} "
          f"libtpu={d['libtpu']}")
    print(f"compile cache: {d['compile_cache_dir']}")
    print(f"host memory the device runtime made resident at backend start "
          f"(outside the agent's memory limit): {d['runtime_rss_bytes']} "
          f"bytes")
    print(f"one process on the chip: the agent (this parent never imports "
          f"jax); workers={facts['workers']} mesh="
          f"{'absent' if facts['mesh'] is None else json.dumps(facts['mesh'])}")
    print(f"kernel family that served the rows: {facts['kernel_family']} "
          f"(the engine's first choice; 0 fallbacks counted)")
    print(f"first-dispatch seconds of {facts['kernel_family']} (compile or "
          f"cache load, included in the windows below): "
          f"{facts['compile_seconds']} over {facts['compiles']} "
          f"geometries {json.dumps(facts['compile_geometries'])}")
    if facts["other_compiled_families"]:
        print(f"other jit families compiled: "
              f"{facts['other_compiled_families']}")
    forced = facts["routing_forced"]
    print("routing: " + (f"FORCED by {forced}" if forced else "default")
          + f"; probe {json.dumps(facts['routing_probe'])}")
    print(f"rows: {json.dumps(facts['rows'])} device_share="
          f"{facts['device_share']} dispatched_total="
          f"{facts['dispatched_total']}")
    print(f"ring geometries: {json.dumps(facts['ring_geometries'])}")
    print(f"sink: {json.dumps(facts['sink'])} — every line once, in order, "
          f"nine fields equal to re.fullmatch; ledger residual "
          f"{facts['ledger_residual']}")
    n, size = facts["windows"]
    print(f"drove {n} windows of {size} bytes ({n * size} bytes of "
          f"{LINE_BYTES}-byte lines)")
    print(f"agent start {facts['agent_start_seconds']} s; seconds until "
          f"each window was in the sink: {facts['window_seconds']}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    deadline = time.monotonic() + DEADLINE_S      # for the whole script

    def drive(routing: str) -> dict:
        work = tempfile.mkdtemp(prefix="chip_smoke_")
        try:
            return run(args.seed, WINDOWS, WINDOW_LINES, routing, True,
                       "tpu", work, deadline)
        finally:
            shutil.rmtree(work, ignore_errors=True)

    try:
        if not os.path.isdir(os.path.join(ROOT, "loongcollector_tpu")):
            raise SmokeFailure(f"{ROOT} holds no loongcollector_tpu package")
        print(f"native rebuilt from source in {build_native():.1f} s",
              flush=True)
        try:
            facts = drive("default")
        except HostRouted as e:
            # The crossover is the engine's design choice and ROADMAP
            # S3/D3's to retune, not this script's: say what default
            # routing did, then hold the device tier to every gate with
            # the existing switch.  The first agent has exited.
            print(f"default routing kept the groups on the host: {e}\n"
                  f"running again with the device tier FORCED "
                  f"(LOONG_NATIVE_T1=0)", flush=True)
            facts = drive("forced")
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", flush=True)
        return 1
    report(facts)
    d = facts["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": d["platform"], "kind": d["device_kind"],
        "count": d["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

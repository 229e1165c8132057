"""The system under test as a child process, and what it says about itself.

Copied from chip_smoke.py (``Agent``, the native rebuild, the log check) and
fitted to the benchmark: the child is started through ``launcher.py`` (the
agent's own ``main`` plus the probes only its process can take), is given the
cores the traffic file leaves it, and its ``/proc`` CPU time is read from
outside.  The parent never imports jax.
"""

from __future__ import annotations

import json
import os
import re
import signal
import subprocess
import sys
import time
import urllib.request

from . import spec

_CLK = os.sysconf("SC_CLK_TCK")
_PAGE = os.sysconf("SC_PAGE_SIZE")
NATIVE_LIBS = ("libloongcollector_native.so", "libloong_ebpf_sim.so")


class AgentFailure(Exception):
    """The agent could not be brought up, or died, or complained."""


def build_native(root: str) -> float:
    """Build ``native/*.so`` on THIS machine unless the checkout already has
    them (the Makefile compiles with -march=native; a library from another
    machine may not run here, and git never carries one).  Seconds spent."""
    t0 = time.monotonic()
    native = os.path.join(root, "native")
    if all(os.path.exists(os.path.join(native, so)) for so in NATIVE_LIBS):
        return 0.0
    r = subprocess.run(["make", "-C", native, "-s", "all"],
                       capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        raise AgentFailure(f"make -C native all failed (rc {r.returncode}):\n"
                           f"{r.stderr[-3000:]}")
    for so in NATIVE_LIBS:
        if not os.path.exists(os.path.join(native, so)):
            raise AgentFailure(f"native build left no {so}")
    return time.monotonic() - t0


def write_config(cfg: dict, cfg_dir: str, data_dir: str, log_path: str,
                 sink_path: str, traced: bool = False) -> dict:
    """The agent's directories from the configuration's files: the pipeline
    with the run's paths filled in (configuration directory), and the
    agent-level flags (data directory, where the agent looks first; in the
    configuration directory it would also be taken for a pipeline).  Returns
    the flags as written."""
    os.makedirs(cfg_dir)
    os.makedirs(data_dir)
    with open(os.path.join(cfg["dir"], cfg["pipeline"])) as f:
        text = f.read()
    text = text.replace("{log_path}", log_path).replace("{sink_path}", sink_path)
    with open(os.path.join(cfg_dir, cfg["pipeline_name"] + ".yaml"), "w") as f:
        f.write(text)
    app = spec.load_json(os.path.join(cfg["dir"], cfg["app_config"]))
    if traced:                  # flags a traced run lays over them, if any
        app.update(cfg.get("app_config_traced") or {})
    with open(os.path.join(data_dir, "loongcollector_config.json"), "w") as f:
        json.dump(app, f)
    return app


def agent_env(cfg: dict, traced: bool) -> dict:
    env = dict(os.environ)
    env.update(spec.load_json(os.path.join(cfg["dir"], cfg["environment"])))
    if traced:
        env["LOONG_TRACE"] = "1"
    return env


def proc_sample(pid: int):
    """(monotonic time, cpu seconds = utime+stime, rss bytes) of ``pid``."""
    with open(f"/proc/{pid}/stat", "rb") as f:
        rest = f.read().rsplit(b")", 1)[1].split()
    return (time.monotonic(), (int(rest[11]) + int(rest[12])) / _CLK,
            int(rest[21]) * _PAGE)


class Agent:
    """``launcher.py <run_dir> --config <dir> --data-dir <dir>`` as a child."""

    def __init__(self, root: str, run_dir: str, cfg_dir: str, data_dir: str,
                 env: dict, cores=None, deadline: float = None):
        self.run_dir = run_dir
        self.log_file = os.path.join(run_dir, "agent.log")
        self.deadline = deadline
        self._log = open(self.log_file, "wb")
        launcher = os.path.join(spec.BENCH_DIR, "launcher.py")
        self.proc = subprocess.Popen(
            [sys.executable, launcher, run_dir,
             "--config", cfg_dir, "--data-dir", data_dir],
            cwd=root, env=env, stdout=self._log, stderr=subprocess.STDOUT,
            preexec_fn=(lambda: os.sched_setaffinity(0, cores))
            if cores else None)
        self.port = None

    def log_text(self) -> str:
        with open(self.log_file, "r", errors="replace") as f:
            return f.read()

    def check_alive(self, doing: str) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise AgentFailure(f"the agent exited with code {rc} while {doing}")
        if self.deadline is not None and time.monotonic() > self.deadline:
            raise AgentFailure(f"out of time while {doing}")

    def wait_endpoint(self) -> None:
        pat = re.compile(r"exposition endpoint on http://127\.0\.0\.1:(\d+)/")
        while self.port is None:
            self.check_alive("starting (no debug endpoint yet)")
            m = pat.search(self.log_text())
            if m:
                self.port = int(m.group(1))
            else:
                time.sleep(0.1)

    def get_text(self, path: str) -> str:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{self.port}{path}", timeout=30) as r:
            return r.read().decode("utf-8", "replace")

    def get(self, path: str) -> dict:
        return json.loads(self.get_text(path))

    def wait_backend(self) -> dict:
        """Block until the agent has named its backend in /debug/status."""
        while True:
            self.check_alive("bringing its backend up")
            dev = self.get("/debug/status").get("device") or {}
            if "platform" in dev:
                return dev
            time.sleep(0.1)

    def sample(self):
        return proc_sample(self.proc.pid)

    def request(self, name: str, answer: str, timeout: float = 120.0) -> dict:
        """Ask the launcher's control thread for ``name``; its answer file."""
        path = os.path.join(self.run_dir, answer)
        if os.path.exists(path):
            os.remove(path)
        open(os.path.join(self.run_dir, name), "w").close()
        t_end = time.monotonic() + timeout
        while not os.path.exists(path):
            self.check_alive(f"waiting for {answer}")
            if time.monotonic() > t_end:
                raise AgentFailure(f"the launcher never answered {name}")
            time.sleep(0.005)
        return spec.load_json(path)

    def stop(self) -> int:
        """SIGTERM, wait for the orderly drain; SIGKILL if it will not go."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=90)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._log.close()
        return self.proc.returncode


def log_complaints(text: str) -> list:
    """The agent's own complaints: a traceback, a critical line, a breach of
    a resource limit.  Each is a line of its log."""
    return [ln for ln in text.splitlines()
            if "Traceback (most recent call last)" in ln
            or "[CRITICAL]" in ln or "watchdog:" in ln]


def parse_metrics(text: str) -> dict:
    """Prometheus text → {name: [(labels, value)]}."""
    out: dict = {}
    rx = re.compile(r'^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$')
    for ln in text.splitlines():
        if not ln or ln[0] == "#":
            continue
        m = rx.match(ln)
        if not m:
            continue
        labels = dict(re.findall(r'(\w+)="([^"]*)"', m.group(2) or ""))
        try:
            out.setdefault(m.group(1), []).append((labels, float(m.group(3))))
        except ValueError:
            continue
    return out


def histogram(metrics: dict, name: str, **labels) -> dict:
    """Cumulative buckets {le: count} of histogram ``name`` with ``labels``."""
    out = {}
    for lab, value in metrics.get(name + "_bucket", []):
        if all(lab.get(k) == v for k, v in labels.items()):
            out[lab["le"]] = out.get(lab["le"], 0.0) + value
    return out

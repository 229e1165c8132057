"""Application-level end-to-end scenarios: the REAL agent process (module
entry point, config watcher, runners, orderly exit) driven over tmp dirs.

The analogue of the reference's e2e scenario suite (test/e2e/test_cases/):
each scenario boots `python -m loongcollector_tpu.application --cpu`,
feeds inputs, and asserts on sink-side evidence — never on queue state.
Subprocess isolation keeps the singletons (FileServer, registries) clean
between scenarios.
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _spawn(config_dir, data_dir):
    env = dict(os.environ)
    env.setdefault("LOONG_DISABLE_INOTIFY", "")  # keep inotify active
    return subprocess.Popen(
        [sys.executable, "-m", "loongcollector_tpu.application", "--cpu",
         "--config", str(config_dir), "--data-dir", str(data_dir)],
        cwd=str(REPO), env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)


from conftest import wait_for


def _wait_for(predicate, timeout=45.0, interval=0.2):
    return wait_for(predicate, timeout=timeout, interval=interval)


def _stop(proc, timeout=20.0):
    proc.send_signal(signal.SIGTERM)
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        pytest.fail("agent did not exit on SIGTERM:\n"
                    + out.decode(errors="replace")[-2000:])
    return out.decode(errors="replace")


@pytest.fixture
def scenario(tmp_path):
    (tmp_path / "conf").mkdir()
    (tmp_path / "data").mkdir()
    (tmp_path / "logs").mkdir()
    (tmp_path / "out").mkdir()
    return tmp_path


class TestTailRestartScenario:
    def test_tail_rotate_restart_no_loss_no_dup(self, scenario):
        """The reference quick-start scenario plus logrotate plus an agent
        restart: every line delivered exactly once across all of it."""
        sink = scenario / "out" / "s.jsonl"
        logf = scenario / "logs" / "app.log"
        (scenario / "conf" / "t.json").write_text(json.dumps({
            "inputs": [{"Type": "input_file",
                        "FilePaths": [str(logf)], "TailExisted": True}],
            "flushers": [{"Type": "flusher_file", "FilePath": str(sink)}],
        }))
        logf.write_text("one\n")
        proc = _spawn(scenario / "conf", scenario / "data")
        try:
            assert _wait_for(lambda: sink.exists()
                             and "one" in sink.read_text())
            with logf.open("a") as f:
                f.write("two\n")
            os.rename(logf, str(logf) + ".1")
            logf.write_text("three\n")
            assert _wait_for(lambda: "three" in sink.read_text())
        finally:
            _stop(proc)
        # restart: append while down, then verify continuity
        with logf.open("a") as f:
            f.write("four\n")
        proc = _spawn(scenario / "conf", scenario / "data")
        try:
            assert _wait_for(lambda: "four" in sink.read_text())
        finally:
            _stop(proc)
        contents = [json.loads(l)["content"]
                    for l in sink.read_text().splitlines()]
        assert sorted(contents) == ["four", "one", "three", "two"], contents


class TestMultilineShutdownScenario:
    def test_open_record_ships_on_sigterm(self, scenario):
        sink = scenario / "out" / "s.jsonl"
        logf = scenario / "logs" / "app.log"
        (scenario / "conf" / "t.json").write_text(json.dumps({
            "inputs": [{"Type": "input_file", "FilePaths": [str(logf)],
                        "TailExisted": True,
                        "Multiline": {"StartPattern": r"\d{4}-.*"}}],
            "flushers": [{"Type": "flusher_file", "FilePath": str(sink)}],
        }))
        # a closed record ahead of the open one: its arrival in the sink is
        # the evidence that the agent is up and has read the file as far as
        # the open record — a sleep says neither (``import jax`` alone can
        # outlast one on a loaded host, and a SIGTERM before the reader has
        # the file finds nothing to drain)
        logf.write_text("2024-01-01 INFO up\n"
                        "2024-01-02 ERROR boom\n  at Foo\n  at Bar\n")
        proc = _spawn(scenario / "conf", scenario / "data")
        try:
            assert _wait_for(lambda: sink.exists() and sink.read_text()
                             .count("\n") >= 1, timeout=90), \
                "the closed record never reached the sink"
            first = sink.read_text().splitlines()
            # the second record is OPEN (no closing start line): it may not
            # ship before the flush timeout; the SIGTERM drain must deliver
            # it whole, and the process must be gone when _stop returns
            assert len(first) == 1, first
        finally:
            out = _stop(proc)
        assert proc.poll() is not None
        recs = [json.loads(ln)["content"]
                for ln in sink.read_text().splitlines()]
        assert recs == ["2024-01-01 INFO up",
                        "2024-01-02 ERROR boom\n  at Foo\n  at Bar"], \
            (recs, out[-1500:])


class TestHTTPIngestScenario:
    def test_ingest_to_file_with_grok(self, scenario):
        import urllib.request
        sink = scenario / "out" / "s.jsonl"
        (scenario / "conf" / "t.json").write_text(json.dumps({
            "inputs": [{"Type": "input_http_server",
                        "Address": "127.0.0.1:18977", "Format": "raw"}],
            "processors": [{"Type": "processor_grok",
                            "Match": "%{LOGLEVEL:lvl} %{GREEDYDATA:msg}"}],
            "flushers": [{"Type": "flusher_file", "FilePath": str(sink)}],
        }))
        proc = _spawn(scenario / "conf", scenario / "data")
        try:
            def _post():
                try:
                    urllib.request.urlopen(urllib.request.Request(
                        "http://127.0.0.1:18977/i",
                        data=b"WARNING disk almost full\n",
                        method="POST"), timeout=2)
                    return True
                except OSError:
                    return False
            assert _wait_for(_post, timeout=30)
            assert _wait_for(lambda: sink.exists() and sink.read_text())
        finally:
            _stop(proc)
        rec = json.loads(sink.read_text().splitlines()[0])
        assert rec["lvl"] == "WARNING"
        assert rec["msg"] == "disk almost full"

"""chip_smoke.py's own logic at a tiny size on the CPU pin, and the
compile-cache rule every JAX-touching process follows.

The chip run itself needs a chip; what tier-1 can hold is everything around
it: the reference comparison, the exactly-once-in-order check, the reading
of /debug/status and /debug/ledger, the non-zero exit on a wrong sink, on a
counted fallback and on a platform that is not ``tpu``.
"""

import ast
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

SEED = 7
WINDOWS = 3
WINDOW_LINES = 2048             # 1 MiB per window: two full reader groups


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """The smoke's deployment, once, at a tiny size, against a real agent
    child on the CPU pin with the device tier forced (on a CPU backend the
    default route is the host walker)."""
    work = str(tmp_path_factory.mktemp("chip_smoke"))
    facts = cs.run(SEED, WINDOWS, WINDOW_LINES, "forced", True, "cpu", work)
    return facts, work


def test_parent_never_imports_jax():
    """One process for each chip: the smoke's parent stays off JAX."""
    tree = ast.parse(open(os.path.join(REPO, "chip_smoke.py")).read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            imported.add(node.module.split(".")[0])
    assert not imported & {"jax", "jaxlib", "loongcollector_tpu"}, imported


def test_lines_are_the_regression_shape():
    data = cs.make_window(SEED, 0, 4000)
    lines = data.split(b"\n")[:-1]
    assert {len(ln) for ln in lines} == {cs.LINE_BYTES - 1}
    rejected = [ln for ln in lines if cs._RX.fullmatch(ln) is None]
    assert 10 <= len(rejected) <= 90          # about 1 %
    assert data == cs.make_window(SEED, 0, 4000)         # from the seed
    assert data != cs.make_window(SEED + 1, 0, 4000)


def test_tiny_run_facts(tiny_run):
    facts, _ = tiny_run
    total = WINDOWS * WINDOW_LINES
    assert facts["sink"]["rows"] == total
    assert facts["sink"]["rejected"] > 0
    assert facts["device"]["platform"] == "cpu"
    assert facts["kernel_family"] == "extract"
    assert facts["rows"]["device"] == total and facts["device_share"] == 1.0
    assert facts["routing_forced"] == ["LOONG_NATIVE_T1=0"]
    assert facts["mesh"] is None
    assert facts["ledger_residual"] == 0
    assert facts["compile_seconds"] > 0
    assert sum(g["real_rows"]
               for g in facts["ring_geometries"].values()) == total


def _tampered(tmp_path, work, edit):
    with open(os.path.join(work, "sink.jsonl"), "rb") as f:
        rows = f.read().split(b"\n")[:-1]
    edit(rows)
    bad = tmp_path / "sink.jsonl"
    bad.write_bytes(b"\n".join(rows) + b"\n")
    return str(bad)


@pytest.mark.parametrize("name,edit", [
    ("lost", lambda rows: rows.pop(100)),
    ("duplicated", lambda rows: rows.insert(100, rows[100])),
    ("duplicated_at_end", lambda rows: rows.append(rows[-1])),
    ("reordered", lambda rows: rows.__setitem__(
        slice(100, 102), [rows[101], rows[100]])),
    ("misparsed", lambda rows: rows.__setitem__(
        100, rows[100].replace(b'"method": "', b'"method": "X'))),
])
def test_wrong_sink_fails(tiny_run, tmp_path, name, edit):
    _, work = tiny_run
    log = os.path.join(work, "access.log")
    assert cs.check_sink(os.path.join(work, "sink.jsonl"), log)["rows"] \
        == WINDOWS * WINDOW_LINES
    with pytest.raises(cs.SmokeFailure):
        cs.check_sink(_tampered(tmp_path, work, edit), log)


def _good_docs(rows=1000):
    status = {
        "device": {
            "platform": "tpu", "device_kind": "TPU v5 lite",
            "device_count": 1, "jax": "0", "jaxlib": "0", "libtpu": "0",
            "compile_cache_dir": "/x", "runtime_rss_bytes": 13 << 30,
            "dispatched_total": 1,
            "routing": {
                "forced": [], "probe": {"latency_s": 0.001,
                                        "bandwidth_Bps": 5e9,
                                        "crossover_bytes": 400000},
                "rows": {"host_walker": 0, "cpu_re": 0},
                "kernel_first_choice": "extract_pallas",
                "kernel_fallbacks_total": 0}},
        "compile": {"extract_pallas": {
            "compiles": 1, "cache_hits": 0, "compile_ms_total": 2000.0,
            "geometries": {"1024x512,1024": {"compiles": 1,
                                             "last_ms": 2000.0}}}},
        "streaming": {"ring": {"real_rows": rows},
                      "geometries": {"1024x512": {
                          "packs": 1, "real_rows": rows, "padded_rows": 24}}},
        "workers": {"count": 4},
    }
    ledger = {"enabled": True, "inflight_live": 0,
              "pipelines": {cs.PIPELINE: {
                  "residual": 0,
                  "boundaries": {"send_ok": {"events": rows}}}},
              "auditor": {"residual_alarms_total": 0}}
    return status, ledger


def test_judge_status_accepts_a_clean_chip_run():
    status, ledger = _good_docs()
    facts = cs.judge_status(status, ledger, 1000)
    assert facts["kernel_family"] == "extract_pallas"
    assert facts["compile_seconds"] == 2.0
    assert facts["device_share"] == 1.0


def _set(path, value):
    def edit(status, ledger):
        doc = {"status": status, "ledger": ledger}
        *head, last = path.split(".")
        for key in head:
            doc = doc[key]
        doc[last] = value
    return edit


@pytest.mark.parametrize("edit", [
    _set("status.device.platform", "cpu"),
    _set("status.device.routing.kernel_fallbacks_total", 1),
    _set("status.mesh", {"lane_count": 4}),
    _set("status.compile.extract", {"compiles": 1, "compile_ms_total": 1.0,
                                    "geometries": {}}),
    _set("status.streaming.ring.real_rows", 899),
    _set("ledger.inflight_live", 3),
    _set("ledger.auditor.residual_alarms_total", 1),
    _set("ledger.enabled", False),
], ids=["platform", "kernel_fallback", "mesh",
        "second_family_served", "device_share", "inflight",
        "auditor_alarm", "ledger_off"])
def test_judge_status_fails(edit):
    status, ledger = _good_docs()
    edit(status, ledger)
    with pytest.raises(cs.SmokeFailure):
        cs.judge_status(status, ledger, 1000)


def test_lane_respill_fails_where_lanes_exist():
    """With several chips visible (one_chip=False) a mesh section is
    expected; a row a lane respilled to the host is a counted fallback."""
    status, ledger = _good_docs()
    status["mesh"] = {"lane_count": 2, "lanes": [
        {"chip": 0, "respilled_events": 0}, {"chip": 1, "respilled_events": 0}]}
    assert cs.judge_status(status, ledger, 1000, one_chip=False)["mesh"]
    status["mesh"]["lanes"][1]["respilled_events"] = 5
    with pytest.raises(cs.SmokeFailure, match="5 rows respilled"):
        cs.judge_status(status, ledger, 1000, one_chip=False)


@pytest.mark.parametrize("line", [
    "Traceback (most recent call last):",
    "[2026-09-26 11:43:56] [CRITICAL] [loong.application] resource limit "
    "breached: rss 13918 MB > limit 2048 MB — exiting for restart",
    "[2026-09-26 11:43:47] [WARNING] [loong.watchdog] watchdog: rss 13831 "
    "MB > limit 2048 MB",
])
def test_agent_log_complaints_fail(line):
    cs.check_agent_log("[INFO] fine\n[WARNING] [loong.flight] dump\n")
    with pytest.raises(cs.SmokeFailure, match="complained"):
        cs.check_agent_log("[INFO] fine\n" + line + "\n[INFO] after\n")


def test_ledger_residual_fails():
    status, ledger = _good_docs()
    ledger["pipelines"][cs.PIPELINE]["residual"] = 2
    with pytest.raises(cs.SmokeFailure, match="not conserved"):
        cs.judge_status(status, ledger, 1000)


def test_host_routed_is_told_apart_from_forced():
    """Under default routing a low device share is the crossover's doing
    (HostRouted: the smoke re-runs forced); once forced it is a failure."""
    status, ledger = _good_docs()
    status["device"]["routing"]["rows"].update(host_walker=900)
    status["streaming"]["ring"]["real_rows"] = 100
    with pytest.raises(cs.HostRouted):
        cs.judge_status(status, ledger, 1000)
    status["device"]["routing"]["forced"] = ["LOONG_NATIVE_T1=0"]
    with pytest.raises(cs.SmokeFailure) as exc:
        cs.judge_status(status, ledger, 1000)
    assert not isinstance(exc.value, cs.HostRouted)


def test_default_routing_on_the_host_raises_host_routed(tmp_path):
    """A real agent whose default routing keeps every group on the host
    walker (here: the CPU backend's host mode) is reported as HostRouted,
    the case the smoke answers by re-running with the tier forced."""
    with pytest.raises(cs.HostRouted, match="host walker 1024"):
        cs.run(SEED, 1, 1024, "default", True, "cpu", str(tmp_path))


def test_main_fails_loudly_without_an_accelerator(monkeypatch, capsys):
    """The command line at its one size where JAX finds no accelerator:
    non-zero exit, the reason last, no result line.  The native rebuild is
    stubbed — `make clean` in the live checkout would pull the library out
    from under the suite's other agent children."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(cs, "build_native", lambda: 0.0)
    assert cs.main(["--seed", "0"]) != 0
    out = capsys.readouterr().out
    last = out.strip().splitlines()[-1]
    assert last.startswith("chip_smoke FAILED: platform is 'cpu'"), out
    assert '"ok"' not in out


def test_cli_takes_the_seed_and_nothing_else():
    """One size, one mode: a pass cannot be a toy-width pass."""
    for flag in ("--windows", "--window-lines", "--routing", "--all-chips"):
        with pytest.raises(SystemExit):
            cs.main([flag, "1"])


def test_cli_fails_alone_in_a_directory(tmp_path):
    """chip_smoke.py without the program beside it: non-zero, no result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = subprocess.run([sys.executable, str(tmp_path / "chip_smoke.py")],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=60)
    assert r.returncode != 0
    assert "holds no loongcollector_tpu package" in r.stdout
    assert '"ok"' not in r.stdout


def test_agent_refuses_an_unpinned_cpu(tmp_path):
    """No chip and no pin: the agent exits non-zero and says why, instead
    of carrying on on the host."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)       # jax falls to the CPU by itself
    (tmp_path / "cfg").mkdir()
    r = subprocess.run(
        [sys.executable, "-m", "loongcollector_tpu",
         "--config", str(tmp_path / "cfg"),
         "--data-dir", str(tmp_path / "data")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 2, r.stderr[-2000:]
    assert "JAX found no accelerator" in r.stderr


# ---------------------------------------------------------------------------
# the compile-cache rule


@pytest.fixture
def config_updates(monkeypatch):
    import jax
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_env_set_is_left_alone(monkeypatch, tmp_path,
                                             config_updates):
    from loongcollector_tpu.ops import device_info
    monkeypatch.setenv(device_info.ENV_CACHE_DIR, str(tmp_path))
    assert device_info.configure_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in dict(config_updates)


def test_compile_cache_unset_is_the_fixed_checkout_path(monkeypatch,
                                                        config_updates):
    from loongcollector_tpu.ops import device_info
    monkeypatch.delenv(device_info.ENV_CACHE_DIR, raising=False)
    path = device_info.configure_compile_cache()
    assert path == device_info.DEFAULT_CACHE_DIR
    assert dict(config_updates)["jax_compilation_cache_dir"] == path
    assert os.path.dirname(path) == REPO          # inside the checkout
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert os.path.basename(path) + "/" in ignored


def test_the_cache_was_written_where_the_rule_says(tiny_run):
    """The agent child of the tiny run placed its cache by the rule."""
    facts, _ = tiny_run
    from loongcollector_tpu.ops import device_info
    want = os.environ.get(device_info.ENV_CACHE_DIR) \
        or device_info.DEFAULT_CACHE_DIR
    assert facts["device"]["compile_cache_dir"] == want
    assert os.listdir(want)


# ---------------------------------------------------------------------------
# the self-watchdog on a chip host


def test_watchdog_excludes_the_device_runtimes_resident_memory(monkeypatch):
    """On a v5e host the TPU runtime alone holds ~13 GB resident from
    backend start; counted against the 2 GB limit it made the agent exit
    "for restart" ten seconds after it came up.  The limit applies to what
    the agent grows by above that."""
    from loongcollector_tpu.monitor.watchdog import LoongCollectorMonitor
    from loongcollector_tpu.ops import device_info
    hits = []
    wd = LoongCollectorMonitor(on_limit_breach=hits.append)
    try:
        monkeypatch.setattr(device_info, "_info",
                            {"runtime_rss_bytes": 13 << 30})
        for _ in range(12):
            wd._check_limits(0.0, (14 << 30), 2.0, 2048 << 20)
        assert hits == []                      # 1 GB of its own: healthy
        for _ in range(10):
            wd._check_limits(0.0, (16 << 30), 2.0, 2048 << 20)
        assert len(hits) == 1 and "rss 3072 MB > limit 2048 MB" in hits[0]
        assert "13312 MB excluded" in hits[0]
        monkeypatch.setattr(device_info, "_info", None)   # no backend yet
        assert device_info.runtime_rss_bytes() == 0
    finally:
        wd.stop()

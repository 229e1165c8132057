"""/debug/status ``threads``: the kernel's account of every thread of the
process, read when the page is asked for and at no other time."""

import threading
import time

import pytest

from loongcollector_tpu.monitor import exposition

FIELDS = ("tid", "cpu_s", "runq_wait_s", "timeslices", "voluntary_switches",
          "involuntary_switches", "last_cpu")


@pytest.fixture()
def named_thread():
    stop = threading.Event()

    def work():
        while not stop.is_set():
            sum(range(2000))
            stop.wait(0.001)
    th = threading.Thread(target=work, name="processor-0", daemon=True)
    th.start()
    yield th
    stop.set()
    th.join(timeout=5)


def test_the_section_is_on_the_page_and_registered(named_thread):
    doc = exposition.collect_status()
    assert "threads" in doc and "threads" in exposition.STATUS_SECTIONS
    assert set(doc["threads"]) == {"at_s", "by_name", "other"}
    assert "MainThread" in doc["threads"]["by_name"]


def test_at_s_is_unrounded_and_moves_with_the_clock():
    a = exposition.threads_status()["at_s"]
    time.sleep(0.011)
    b = exposition.threads_status()["at_s"]
    assert isinstance(a, float) and 0.011 <= b - a < 1.0
    assert round(a, 1) != a or round(b, 1) != b


@pytest.mark.parametrize("field", FIELDS)
def test_a_named_thread_has_every_field_the_kernel_gives(named_thread, field):
    time.sleep(0.03)
    row = exposition.threads_status()["by_name"]["processor-0"]
    assert field in row
    assert row["tid"] == named_thread.native_id
    assert row[field] >= 0


def test_the_counters_only_grow_and_cpu_is_the_threads_own(named_thread):
    first = exposition.threads_status()["by_name"]["processor-0"]
    time.sleep(0.1)
    later = exposition.threads_status()["by_name"]["processor-0"]
    for k in ("cpu_s", "runq_wait_s", "timeslices", "voluntary_switches",
              "involuntary_switches"):
        assert later[k] >= first[k], k
    assert later["cpu_s"] > first["cpu_s"]
    assert later["voluntary_switches"] > first["voluntary_switches"]
    # a thread that sleeps between bursts is not charged the sleeping
    assert later["cpu_s"] - first["cpu_s"] < 0.1


def test_two_threads_of_one_name_both_appear(named_thread):
    stop = threading.Event()
    twin = threading.Thread(target=stop.wait, name="processor-0", daemon=True)
    twin.start()
    try:
        by = exposition.threads_status()["by_name"]
        names = [n for n in by if n.split("#")[0] == "processor-0"]
        assert len(names) == 2
        assert {by[n]["tid"] for n in names} == {named_thread.native_id,
                                                 twin.native_id}
    finally:
        stop.set()
        twin.join(timeout=5)


def test_other_counts_the_tasks_that_are_no_python_thread(monkeypatch,
                                                          tmp_path):
    """A task directory with one Python thread and two runtime tasks."""
    me = threading.get_native_id()
    for tid, sched in ((me, "5000000 1000000 7"), (900001, "2000000000 "
                       "500000000 11"), (900002, "1000000000 0 3")):
        d = tmp_path / str(tid)
        d.mkdir()
        (d / "schedstat").write_text(sched + "\n")
        (d / "status").write_text("voluntary_ctxt_switches:\t4\n"
                                  "nonvoluntary_ctxt_switches:\t2\n")
        (d / "stat").write_text(f"{tid} (a (b) c) S " + " ".join(
            ["0"] * 35 + ["5"]) + " 0 0\n")
    monkeypatch.setattr(exposition, "_TASK_DIR", str(tmp_path))
    monkeypatch.setattr(threading, "enumerate",
                        lambda: [threading.current_thread()])
    doc = exposition.threads_status()
    assert doc["by_name"] == {threading.current_thread().name: {
        "tid": me, "cpu_s": 0.005, "runq_wait_s": 0.001, "timeslices": 7,
        "voluntary_switches": 4, "involuntary_switches": 2, "last_cpu": 5}}
    assert doc["other"] == {"threads": 2, "cpu_s": 3.0, "runq_wait_s": 0.5}


@pytest.mark.parametrize("sched", [None, "0 0 0\n"])
def test_fields_the_kernel_does_not_give_are_absent_never_zero(
        monkeypatch, tmp_path, sched):
    """The chip hosts' sandboxed kernel: no schedstat (or an all-zero one),
    no switch counts — no run-queue wait, timeslices or switches on the
    page, and cpu_s from stat's utime + stime ticks."""
    me = threading.get_native_id()
    d = tmp_path / str(me)
    d.mkdir()
    if sched is not None:
        (d / "schedstat").write_text(sched)
    (d / "status").write_text("Name:\tpython3\nState:\tR (running)\n")
    (d / "stat").write_text(f"{me} (python3) R 1 1 1 0 0 0 0 0 0 0 296 7 "
                            + " ".join(["0"] * 24) + " 0 0\n")
    monkeypatch.setattr(exposition, "_TASK_DIR", str(tmp_path))
    monkeypatch.setattr(threading, "enumerate",
                        lambda: [threading.current_thread()])
    row = exposition.threads_status()["by_name"][
        threading.current_thread().name]
    assert row == {"tid": me, "cpu_s": pytest.approx(3.03), "last_cpu": 0}


def test_a_thread_that_ended_meanwhile_is_a_row_of_its_id_alone(
        monkeypatch, tmp_path):
    """Between `threading.enumerate()` and the reads a thread may end: its
    files are gone, nothing is asked of the dead thread itself."""
    gone = threading.Thread(target=lambda: None, name="gone")
    gone.start()
    gone.join()
    monkeypatch.setattr(exposition, "_TASK_DIR", str(tmp_path))  # empty
    monkeypatch.setattr(threading, "enumerate", lambda: [gone])
    monkeypatch.delattr(time, "pthread_getcpuclockid")    # never asked
    assert exposition.threads_status()["by_name"] == {
        "gone": {"tid": gone.native_id}}


def test_no_thread_is_started_and_nothing_runs_between_scrapes():
    before = {t.ident for t in threading.enumerate()}
    exposition.threads_status()
    exposition.collect_status()
    assert {t.ident for t in threading.enumerate()} == before

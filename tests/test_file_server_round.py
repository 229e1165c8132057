"""The file server's round under spans: a round that read a group is an
``input.file.round`` with the read, the push and the checkpoint under it on
the reader's own thread; a round that read nothing leaves no span."""

import threading

import pytest

from loongcollector_tpu import trace
from loongcollector_tpu.input.file.file_server import (FileServer,
                                                       _ConfigState)
from loongcollector_tpu.input.file.polling import FileDiscoveryConfig

ROUND = "input.file.round"
CHILDREN = ("input.file.read", "input.file.push", "input.file.checkpoint")


class _PQM:
    def __init__(self, valid=True, accept=True):
        self.valid, self.accept, self.pushed = valid, accept, []

    def is_valid_to_push(self, key):
        return self.valid

    def push_queue(self, key, group):
        if self.accept:
            self.pushed.append(group)
        return self.accept

    def get_queue(self, key):
        return None


@pytest.fixture(autouse=True)
def _clean():
    trace.disable()
    yield
    trace.disable()


def _server(tmp_path, pqm, content=b"one\ntwo\nthree\n", tags=None):
    fs = FileServer()
    fs.checkpoints.path = str(tmp_path / "checkpoints.json")
    path = tmp_path / "in.log"
    path.write_bytes(content)
    st = _ConfigState("t", FileDiscoveryConfig([str(path)]), queue_key=1,
                      tail_existing=True)
    st.tag_provider = tags
    fs._configs["t"] = st
    fs.process_queue_manager = pqm
    return fs, path


def _by_name(t):
    out: dict = {}
    for s in t.finished_spans():
        out.setdefault(s.name, []).append(s)
    return out


def test_a_round_that_moved_a_group_has_read_push_and_checkpoint_under_it(
        tmp_path):
    pqm = _PQM()
    fs, _path = _server(tmp_path, pqm, tags=lambda p: {"k": "v"})
    t = trace.enable()
    assert fs._round() is True
    by = _by_name(t)
    (rnd,) = by[ROUND]
    assert rnd.parent_id is None and rnd.attrs["reads"] == 1
    assert len(pqm.pushed) == 1
    for name in CHILDREN:
        (sp,) = by[name]
        assert sp.parent_id == rnd.span_id, name
        assert sp.tid == rnd.tid == threading.get_native_id()
        assert sp.cpu_s is not None and sp.cpu_s <= sp.duration_s + 1e-5
        assert rnd._start_perf <= sp._start_perf
        assert sp._start_perf + sp.duration_s \
            <= rnd._start_perf + rnd.duration_s + 1e-6
    assert by["input.file.push"][0].attrs["rejected"] is False
    assert rnd.cpu_s >= sum(by[n][0].cpu_s for n in CHILDREN) - 1e-5
    assert t.current_span() is None            # the round popped itself


def test_the_first_rounds_discovery_is_under_it_too(tmp_path):
    fs, _path = _server(tmp_path, _PQM())
    t = trace.enable()
    fs._round()
    by = _by_name(t)
    (disc,) = by["input.file.discover"]
    assert disc.parent_id == by[ROUND][0].span_id
    assert disc.attrs["config"] == "t" and disc.cpu_s <= disc.duration_s + 1e-5
    # it ends before the read it made possible starts
    assert disc._start_perf + disc.duration_s \
        <= by["input.file.read"][0]._start_perf + 1e-6


@pytest.mark.parametrize("why", ["nothing new", "queue over its watermark"])
def test_an_idle_round_leaves_no_span(tmp_path, why):
    pqm = _PQM()
    fs, path = _server(tmp_path, pqm)
    fs._round()                                # takes the file in, untraced
    if why == "queue over its watermark":
        pqm.valid = False
        with open(path, "ab") as f:
            f.write(b"four\n")
    t = trace.enable()
    fs._round()
    assert t.finished_spans() == [] and t.current_span() is None
    assert fs.stats.reads_blocked_total == (why != "nothing new")


def test_a_rejected_push_says_so_and_has_no_checkpoint(tmp_path):
    fs, _path = _server(tmp_path, _PQM(accept=False))
    t = trace.enable()
    assert fs._round() is False                # read, pushed, taken back
    by = _by_name(t)
    assert by["input.file.push"][0].attrs["rejected"] is True
    assert "input.file.checkpoint" not in by
    assert by[ROUND][0].attrs["reads"] == 1 and fs.stats.push_rejected_total == 1


def test_every_group_of_a_round_gets_its_own_three(tmp_path):
    fs, _path = _server(tmp_path, _PQM(), content=b"x" * 99 + b"\n")
    st = fs._configs["t"]
    st.chunk_size = 100                        # one line a read
    with open(_path, "ab") as f:
        f.write((b"y" * 99 + b"\n") * 4)
    t = trace.enable()
    fs._round()
    by = _by_name(t)
    (rnd,) = by[ROUND]
    assert rnd.attrs["reads"] == len(by["input.file.read"]) >= 2
    for name in CHILDREN:
        assert len(by[name]) == rnd.attrs["reads"]
        assert all(s.parent_id == rnd.span_id for s in by[name])


def test_tracing_off_the_round_is_the_round(tmp_path):
    pqm = _PQM()
    fs, _path = _server(tmp_path, pqm)
    assert fs._round() is True and len(pqm.pushed) == 1
    assert fs.stats.reads_total == 1


def test_the_rounds_spans_do_not_change_the_structure_of_two_runs(tmp_path):
    def run(sub):
        d = tmp_path / sub
        d.mkdir()
        fs, _path = _server(d, _PQM())
        t = trace.enable()
        fs._round()
        fs._round()                            # idle: nothing more
        names = sorted(s.name for s in t.finished_spans())
        trace.disable()
        return names
    assert run("a") == run("b") == sorted(
        (ROUND, "input.file.discover") + CHILDREN)

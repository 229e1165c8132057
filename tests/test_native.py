"""Native C++ data plane: differential tests vs the Python fallbacks."""

import numpy as np
import pytest

import loongcollector_tpu.native as native
from loongcollector_tpu.models import PipelineEventGroup, SourceBuffer
from loongcollector_tpu.pipeline.serializer.sls_serializer import \
    SLSEventGroupSerializer

pytestmark = pytest.mark.skipif(native.get_lib() is None,
                                reason="native library unavailable")


class TestSplitLines:
    @pytest.mark.parametrize("data", [
        b"a\nbb\nccc\n", b"a\nbb", b"\n\n", b"a\n\nb\n", b"single",
        b"trailing\n",
    ])
    def test_matches_python(self, data):
        seg = np.frombuffer(data, dtype=np.uint8)
        offs, lens = native.split_lines(seg, ord("\n"), 100)
        # python reference
        nl = np.nonzero(seg == ord("\n"))[0].astype(np.int64)
        starts = np.concatenate([[0], nl + 1])
        ends = np.concatenate([nl, [len(seg)]])
        if len(starts) > 1 and starts[-1] >= len(seg):
            starts, ends = starts[:-1], ends[:-1]
        assert list(offs) == list(starts + 100)
        assert list(lens) == list(ends - starts)


class TestPackRows:
    def test_matches_numpy(self):
        rng = np.random.default_rng(3)
        arena = rng.integers(1, 255, 1000, dtype=np.uint8)
        offsets = np.array([0, 100, 950], dtype=np.int64)
        lengths = np.array([50, 300, 50], dtype=np.int32)
        rows = native.pack_rows(arena, offsets, lengths, L=128, B=8)
        assert rows.shape == (8, 128)
        assert bytes(rows[0, :50].tobytes()) == bytes(arena[:50].tobytes())
        assert (rows[0, 50:] == 0).all()
        # length > L truncates
        assert bytes(rows[1].tobytes()) == bytes(arena[100:228].tobytes())
        # padding rows zero
        assert (rows[3:] == 0).all()


class TestSlsSerialize:
    def test_matches_python_serializer(self, monkeypatch):
        g = PipelineEventGroup()
        sb = g.source_buffer
        data = b"alpha beta\ngamma delta\n"
        sb.copy_string(data)
        from loongcollector_tpu.models import ColumnarLogs
        cols = ColumnarLogs(np.array([0, 11]), np.array([10, 11]),
                            np.array([1700000001, 1700000002]))
        v = sb.copy_string(b"value-x")
        cols.set_field("f1", np.array([0, v.offset]), np.array([5, v.length]))
        cols.set_field("f2", np.array([6, 0]), np.array([4, -1]))  # absent 2nd
        cols.content_consumed = True
        g.set_columns(cols)
        ser = SLSEventGroupSerializer()
        native_bytes = ser.serialize([g])
        # force the python fallback and compare
        monkeypatch.setattr(native, "sls_serialize",
                            lambda *a, **k: None)
        python_bytes = ser.serialize([g])
        assert native_bytes == python_bytes

    def test_content_column_included(self, monkeypatch):
        g = PipelineEventGroup()
        sb = g.source_buffer
        sb.copy_string(b"line-one\n")
        from loongcollector_tpu.models import ColumnarLogs
        cols = ColumnarLogs(np.array([0]), np.array([8]), np.array([1700000000]))
        v = sb.copy_string(b"extra")
        cols.set_field("tagf", np.array([v.offset]), np.array([v.length]))
        g.set_columns(cols)  # content NOT consumed
        ser = SLSEventGroupSerializer()
        native_bytes = ser.serialize([g])
        monkeypatch.setattr(native, "sls_serialize", lambda *a, **k: None)
        assert native_bytes == ser.serialize([g])
        assert b"line-one" in native_bytes


class TestNativeJsonExtract:
    def _run(self, lines, keys):
        blob = b"".join(lines)
        arena = np.frombuffer(blob, np.uint8)
        lens = np.array([len(l) for l in lines], np.int32)
        offs = np.concatenate([[0], np.cumsum(lens[:-1])]).astype(np.int64)
        return native.json_extract(arena, offs, lens, keys), arena

    def test_scalar_spans(self):
        lines = [b'{"a": 1, "b": "x", "c": true, "d": null, "e": -1.5e3}']
        (offs, lens, ok, fb), arena = self._run(lines, [b"a", b"b", b"c",
                                                        b"d", b"e"])
        assert ok[0] and not fb[0]
        def val(f):
            return bytes(arena[offs[f,0]:offs[f,0]+lens[f,0]].tobytes())
        assert val(0) == b"1"
        assert val(1) == b"x"
        assert val(2) == b"true"
        assert val(3) == b"null"
        assert val(4) == b"-1.5e3"

    def test_nested_raw_span(self):
        lines = [b'{"o": {"x": [1, "}"]}, "t": "y"}']
        (offs, lens, ok, fb), arena = self._run(lines, [b"o", b"t"])
        assert ok[0]
        raw = bytes(arena[offs[0,0]:offs[0,0]+lens[0,0]].tobytes())
        assert raw == b'{"x": [1, "}"]}'

    def test_escape_falls_back(self):
        lines = [b'{"a": "has \\" quote"}', b'{"a": "plain"}']
        (offs, lens, ok, fb), arena = self._run(lines, [b"a"])
        assert fb[0] and not ok[0]
        assert ok[1] and not fb[1]

    def test_unknown_key_falls_back(self):
        lines = [b'{"a": 1, "zz": 2}']
        (offs, lens, ok, fb), _ = self._run(lines, [b"a"])
        assert fb[0]

    def test_malformed_falls_back(self):
        lines = [b'{"a": }', b'not json', b'[1,2]', b'{}']
        (offs, lens, ok, fb), _ = self._run(lines, [b"a"])
        assert fb[0] and fb[1] and fb[2]
        assert ok[3]  # empty object is fine

    def test_processor_mixed_fastpath_and_fallback(self):
        from loongcollector_tpu.pipeline.plugin.interface import PluginContext
        from loongcollector_tpu.processor.parse_json import ProcessorParseJson
        from loongcollector_tpu.processor.split_log_string import \
            ProcessorSplitLogString
        from loongcollector_tpu.models import PipelineEventGroup, SourceBuffer
        data = (b'{"k": "v1", "n": 1}\n'
                b'{"k": "esc\\"aped", "n": 2}\n'     # fallback (escape)
                b'{"k": "v3", "n": 3, "extra": 9}\n'  # fallback (new key)
                b'broken\n')
        sb = SourceBuffer(len(data) + 64)
        view = sb.copy_string(data)
        g = PipelineEventGroup(sb)
        g.add_raw_event(1).set_content(view)
        ctx = PluginContext("t")
        sp = ProcessorSplitLogString(); sp.init({}, ctx); sp.process(g)
        pj = ProcessorParseJson(); pj.init({}, ctx); pj.process(g)
        evs = g.materialize()
        assert evs[0].get_content(b"k") == b"v1"
        assert evs[1].get_content(b"k") == b'esc"aped'   # unescaped via host
        assert evs[2].get_content(b"extra") == b"9"
        assert evs[3].get_content(b"rawLog") == b"broken"

    def test_strict_rejections(self):
        lines = [b'{} trailing', b'{"a": truX}', b'{"a": {]}}',
                 b'{"a": 01}', b'{"a": 1.}', b'{"a": 1e}', b'{"a": -0.5e+2}']
        (offs, lens, ok, fb), _ = self._run(lines, [b"a"])
        assert fb[0] and fb[1] and fb[2] and fb[3] and fb[4] and fb[5]
        assert ok[6]  # valid exotic number stays fast-path

    def test_control_char_falls_back(self):
        lines = [b'{"a": "x\x01y"}', b'{"a": "clean"}']
        (offs, lens, ok, fb), _ = self._run(lines, [b"a"])
        assert fb[0] and not ok[0]  # host json.loads also rejects this
        assert ok[1]


class TestAppendFile:
    """The file sink's write as one native call: open(path, "ab"), write
    all, close."""

    @pytest.mark.parametrize("wrap", [bytes, memoryview, bytearray],
                             ids=["bytes", "memoryview", "bytearray"])
    def test_creates_then_appends(self, tmp_path, wrap):
        path = tmp_path / "sink.jsonl"
        assert native.append_file(str(path), wrap(b"one\n"))
        assert native.append_file(str(path), wrap(b"two\n"))
        assert native.append_file(str(path), wrap(b""))
        assert path.read_bytes() == b"one\ntwo\n"

    def test_a_numpy_view_is_written_whole(self, tmp_path):
        payload = np.frombuffer(b"x" * (3 << 20), dtype=np.uint8)
        assert native.append_file(str(tmp_path / "big"),
                                  memoryview(payload)[: (3 << 20) - 7])
        assert (tmp_path / "big").stat().st_size == (3 << 20) - 7

    @pytest.mark.parametrize("target,exc", [
        ("", IsADirectoryError), ("missing/dir/sink", FileNotFoundError)])
    def test_a_failed_call_raises_its_errno(self, tmp_path, target, exc):
        with pytest.raises(exc) as err:
            native.append_file(str(tmp_path / target), b"x")
        assert err.value.filename == str(tmp_path / target)

    def test_no_library_no_write(self, tmp_path, monkeypatch):
        monkeypatch.setattr(native, "get_lib", lambda: None)
        assert native.append_file(str(tmp_path / "sink"), b"x") is False
        assert not (tmp_path / "sink").exists()


class TestLoadFailure:
    def test_a_failed_load_is_kept_and_not_retried(self, monkeypatch):
        """One build/load attempt per process: a failure raises on every
        call, and the 120 s `make` runs once, not once per call site."""
        attempts = []

        def failing_build():
            attempts.append(1)
            raise native.NativeLibraryError("native build failed: boom")

        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_load_attempted", False)
        monkeypatch.setattr(native, "_load_error", None)
        monkeypatch.setattr(native, "_SO_PATH", "/nonexistent/lib.so")
        monkeypatch.delenv("LOONG_NATIVE_LIB", raising=False)
        monkeypatch.setattr(native, "_build", failing_build)
        for _ in range(3):
            with pytest.raises(native.NativeLibraryError, match="boom"):
                native.get_lib()
        assert len(attempts) == 1

    def test_an_unloadable_library_is_a_native_library_error(
            self, monkeypatch, tmp_path):
        bad = tmp_path / "bad.so"
        bad.write_bytes(b"not an ELF file")
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_load_attempted", False)
        monkeypatch.setattr(native, "_load_error", None)
        monkeypatch.setenv("LOONG_NATIVE_LIB", str(bad))
        with pytest.raises(native.NativeLibraryError, match="failed to load"):
            native.get_lib()

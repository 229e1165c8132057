"""loongshard batched-NDJSON goldens: the native zero-copy serialize fast
path must be byte-identical to the canonical per-event dict + json.dumps
loops it replaced (ISSUE 4 satellite) — for the JSON serializer and for the
clickhouse/doris/elasticsearch payload builders, across escaping, absent
fields, tag collisions and non-ASCII fallback."""

import json
from datetime import datetime, timezone

import numpy as np
import pytest

import loongcollector_tpu.native as native
from loongcollector_tpu.flusher.clickhouse import FlusherClickHouse
from loongcollector_tpu.flusher.doris import FlusherDoris
from loongcollector_tpu.flusher.elasticsearch import FlusherElasticsearch
from loongcollector_tpu.models import PipelineEventGroup, SourceBuffer
from loongcollector_tpu.pipeline.plugin.interface import PluginContext
from loongcollector_tpu.pipeline.serializer import batch_json
from loongcollector_tpu.pipeline.serializer.batch_json import (
    TS_EPOCH, TS_ISO8601, dumps_row, native_group_rows, ndjson_payload)
from loongcollector_tpu.pipeline.serializer.event_dicts import \
    iter_event_dicts
from loongcollector_tpu.pipeline.serializer.json_serializer import \
    JsonSerializer
from loongcollector_tpu.processor.parse_regex import ProcessorParseRegex
from loongcollector_tpu.processor.split_log_string import \
    ProcessorSplitLogString


def _columnar_group(lines, tags=(), regex=r"(\w+)-(\d+) (\S+)",
                    keys=("word", "num", "rest")):
    """chunk → split → regex parse: a fields-bearing columnar group, the
    shape the processing pipeline hands to the serializers."""
    data = b"\n".join(lines) + b"\n"
    sb = SourceBuffer(len(data) + 64)
    g = PipelineEventGroup(sb)
    g.add_raw_event(7).set_content(sb.copy_string(data))
    for k, v in tags:
        g.set_tag(k, v)
    ctx = PluginContext("golden")
    sp = ProcessorSplitLogString()
    sp.init({}, ctx)
    sp.process(g)
    pr = ProcessorParseRegex()
    pr.init({"Regex": regex, "Keys": list(keys)}, ctx)
    pr.process(g)
    return g


@pytest.fixture()
def no_native(monkeypatch):
    """Force every consumer onto the canonical dict path."""
    monkeypatch.setattr(native, "ndjson_serialize", lambda *a, **k: None)


LINES = [b"alpha-1 /index.html", b"beta-22 /api/v1", b"gamma-333 /x?q=1"]
TAGS = ((b"host", b"web-1"), (b"__source__", b"fileA"))


class TestJsonSerializerGolden:
    def test_fast_path_is_byte_identical(self, monkeypatch):
        ser = JsonSerializer()
        fast = bytes(ser.serialize([_columnar_group(LINES, TAGS)]))
        monkeypatch.setattr(native, "ndjson_serialize",
                            lambda *a, **k: None)
        slow = bytes(ser.serialize([_columnar_group(LINES, TAGS)]))
        assert fast == slow
        assert fast.count(b"\n") == len(LINES)

    @pytest.mark.parametrize("groups", [
        pytest.param(lambda: [_columnar_group(LINES, TAGS)], id="one_group"),
        pytest.param(lambda: [_columnar_group(LINES, TAGS),
                              _columnar_group(LINES[:1])], id="two_groups"),
        pytest.param(lambda: [_columnar_group([b"caf\xc3\xa9-1 \xff"])],
                     id="dict_path"),
        pytest.param(lambda: [], id="none"),
    ])
    def test_serialize_view_is_serialize_without_the_join(self, groups):
        ser = JsonSerializer()
        view = ser.serialize_view(groups())
        assert bytes(view) == ser.serialize(groups())
        # one native part comes back as the assembler's own buffer
        assert isinstance(view, memoryview) == (len(groups()) == 1
                                                and b"caf" not in bytes(view))

    def test_append_group_writes_what_serialize_gives(self, tmp_path):
        ser, path = JsonSerializer(), str(tmp_path / "sink.jsonl")
        want = ser.serialize([_columnar_group(LINES, TAGS)])
        for _ in range(2):
            nbytes, ser_s, write_s = ser.append_group(
                _columnar_group(LINES, TAGS), path)
            assert nbytes == len(want) and ser_s > 0 and write_s > 0
        scratch = ser._scratch
        assert (tmp_path / "sink.jsonl").read_bytes() == want * 2
        # a larger group grows the kept buffer, a smaller one reuses it
        big = [b"w-%d %s" % (i, b"x" * 300) for i in range(400)]
        assert ser.append_group(_columnar_group(big), path) is not None
        assert len(ser._scratch) > len(scratch)
        scratch = ser._scratch
        assert ser.append_group(_columnar_group(LINES), path) is not None
        assert ser._scratch is scratch
        assert (tmp_path / "sink.jsonl").read_bytes() == (
            want * 2 + ser.serialize([_columnar_group(big)])
            + ser.serialize([_columnar_group(LINES)]))

    @pytest.mark.parametrize("group", [
        # a high byte anywhere in the arena: the span-by-span check and
        # CPython's codec belong to the general path
        pytest.param(lambda: _columnar_group([b"caf\xc3\xa9-1 /x"]),
                     id="high_byte"),
        # a field named like the timestamp key, a field named like a tag
        pytest.param(lambda: _columnar_group(
            LINES, keys=("__time__", "num", "rest")), id="ts_key_collides"),
        pytest.param(lambda: _columnar_group(
            LINES, tags=((b"word", b"t"),)), id="tag_collides"),
        pytest.param(lambda: PipelineEventGroup(SourceBuffer(64)),
                     id="not_columnar"),
    ])
    def test_append_group_declines_and_writes_nothing(self, tmp_path, group):
        path = tmp_path / "sink.jsonl"
        assert JsonSerializer().append_group(group(), str(path)) is None
        assert not path.exists()

    def test_literal_golden(self):
        ser = JsonSerializer()
        out = bytes(ser.serialize([_columnar_group(LINES[:1], TAGS)]))
        assert out == (b'{"host": "web-1", "__source__": "fileA", '
                       b'"__time__": 7, "word": "alpha", "num": "1", '
                       b'"rest": "/index.html"}\n')

    def test_escapes_match_json_dumps(self, monkeypatch):
        lines = [b'esc-1 "quoted"\\back',
                 b"ctl-2 a\tb\x01c",
                 b"del-3 x\x7fy"]
        ser = JsonSerializer()
        fast = bytes(ser.serialize([_columnar_group(lines, TAGS)]))
        monkeypatch.setattr(native, "ndjson_serialize",
                            lambda *a, **k: None)
        slow = bytes(ser.serialize([_columnar_group(lines, TAGS)]))
        assert fast == slow
        assert b'\\"quoted\\"' in fast and b"\\t" in fast \
            and b"\\u0001" in fast

    def test_non_ascii_falls_back_and_matches(self, monkeypatch):
        lines = ["müller-1 ünïcode".encode(), b"plain-2 ok",
                 b"bad-3 \xff\xfe broken"]
        called = []
        orig = native.ndjson_serialize

        def spy(*a, **k):
            called.append(1)
            return orig(*a, **k)
        monkeypatch.setattr(native, "ndjson_serialize", spy)
        ser = JsonSerializer()
        fast = bytes(ser.serialize([_columnar_group(lines, TAGS)]))
        assert not called, "non-ASCII spans must stay on the codec path"
        monkeypatch.setattr(native, "ndjson_serialize",
                            lambda *a, **k: None)
        assert fast == bytes(ser.serialize([_columnar_group(lines, TAGS)]))

    def test_ts_key_collision_falls_back(self, monkeypatch):
        g = _columnar_group(LINES, ((b"__time__", b"tagged"),))
        assert native_group_rows(g, "__time__", ts_mode=TS_EPOCH,
                                 ts_first=True) is None
        ser = JsonSerializer()
        fast = bytes(ser.serialize(
            [_columnar_group(LINES, ((b"__time__", b"tagged"),))]))
        monkeypatch.setattr(native, "ndjson_serialize",
                            lambda *a, **k: None)
        slow = bytes(ser.serialize(
            [_columnar_group(LINES, ((b"__time__", b"tagged"),))]))
        assert fast == slow

    def test_absent_fields_omit_keys(self, monkeypatch):
        # second line fails the pattern → _partial_ routes or absent spans;
        # use a pattern where one group is optional-ish via alternation
        lines = [b"aa-1 x", b"zzz 9"]   # second line: no match
        ser = JsonSerializer()
        fast = bytes(ser.serialize([_columnar_group(lines, TAGS)]))
        monkeypatch.setattr(native, "ndjson_serialize",
                            lambda *a, **k: None)
        slow = bytes(ser.serialize([_columnar_group(lines, TAGS)]))
        assert fast == slow

    def test_event_groups_unchanged(self):
        g = PipelineEventGroup()
        sb = g.source_buffer
        ev = g.add_log_event(11)
        ev.set_content(sb.copy_string(b"k"), sb.copy_string(b"v"))
        g.set_tag(b"host", b"h")
        out = bytes(JsonSerializer().serialize([g]))
        assert out == b'{"host": "h", "__time__": 11, "k": "v"}\n'


class TestNdjsonPayloadGolden:
    def test_clickhouse_identical_and_golden(self, monkeypatch):
        fl = FlusherClickHouse()
        fl._init_sink({"Addresses": ["http://ch:8123"], "Table": "t"})
        fast, _ = fl.build_payload([_columnar_group(LINES[:1], TAGS)])
        monkeypatch.setattr(native, "ndjson_serialize",
                            lambda *a, **k: None)
        slow, _ = fl.build_payload([_columnar_group(LINES[:1], TAGS)])
        assert bytes(fast) == bytes(slow)
        assert bytes(fast) == (
            b'{"host": "web-1", "__source__": "fileA", "word": "alpha", '
            b'"num": "1", "rest": "/index.html", "_timestamp": 7}\n')

    def test_doris_identical(self, monkeypatch):
        fl = FlusherDoris()
        fl._init_sink({"Addresses": ["http://d:8030"], "Database": "db",
                       "Table": "t"})
        fast, _ = fl.build_payload([_columnar_group(LINES, TAGS)])
        monkeypatch.setattr(native, "ndjson_serialize",
                            lambda *a, **k: None)
        slow, _ = fl.build_payload([_columnar_group(LINES, TAGS)])
        assert bytes(fast) == bytes(slow)

    def test_elasticsearch_identical_with_iso_timestamps(self, monkeypatch):
        fl = FlusherElasticsearch()
        fl._init_sink({"Addresses": ["http://es:9200"], "Index": "logs"})
        fast, _ = fl.build_payload([_columnar_group(LINES, TAGS)])
        monkeypatch.setattr(native, "ndjson_serialize",
                            lambda *a, **k: None)
        slow, _ = fl.build_payload([_columnar_group(LINES, TAGS)])
        assert bytes(fast) == bytes(slow)
        assert bytes(fast).count(b'{"index": {"_index": "logs"}}') \
            == len(LINES)
        assert b'"@timestamp": "1970-01-01T00:00:07Z"' in bytes(fast)

    def test_mixed_fast_and_fallback_groups(self, monkeypatch):
        groups = [_columnar_group(LINES, TAGS),
                  _columnar_group(["ü-1 x".encode()], TAGS)]
        fast = ndjson_payload(groups, ts_key="_timestamp")
        monkeypatch.setattr(native, "ndjson_serialize",
                            lambda *a, **k: None)
        groups = [_columnar_group(LINES, TAGS),
                  _columnar_group(["ü-1 x".encode()], TAGS)]
        slow = ndjson_payload(groups, ts_key="_timestamp")
        assert bytes(fast) == bytes(slow)

    def test_empty_groups_yield_none(self):
        assert ndjson_payload([]) is None


class TestIso8601Native:
    @pytest.mark.parametrize("ts", [0, 7, 951868800, 1700000000,
                                    4102444799, 1583020799, 253402300799])
    def test_matches_datetime(self, ts):
        g = _columnar_group([b"aa-%d x" % (ts % 97)])
        out = native_group_rows(g, "@timestamp", ts_mode=TS_ISO8601,
                                ts_first=False)
        # group timestamps are the split timestamp (7); patch in the
        # parametrised one via the columns and re-serialize
        g.columns.timestamps = np.full(len(g.columns), ts, dtype=np.int64)
        out = native_group_rows(g, "@timestamp", ts_mode=TS_ISO8601,
                                ts_first=False)
        want = datetime.fromtimestamp(
            ts, tz=timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ")
        assert f'"@timestamp": "{want}"'.encode() in bytes(out)


class TestSharedRowEncoder:
    def test_dumps_row_is_canonical(self):
        obj = {"a": 1, "b": "x\ty", "c": "ünïcode"}
        assert dumps_row(obj) == json.dumps(
            obj, ensure_ascii=False).encode()

    def test_iter_event_dicts_round_trip(self):
        g = _columnar_group(LINES, TAGS)
        rows = list(iter_event_dicts(g))
        assert len(rows) == len(LINES)
        assert rows[0][1]["word"] == "alpha"

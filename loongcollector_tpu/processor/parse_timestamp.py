"""processor_parse_timestamp — event-time rewrite from a time field.

Reference: core/plugin/processor/ProcessorParseTimestampNative.cpp
(strptime-class parsing via common/Strptime.h, rewrites event timestamps).

Host execution, two paths with one answer.  The row path (`_parse_one`) is
`time.strptime` + `time.mktime` / `calendar.timegm` per value behind a
per-process memo of distinct strings; it defines the result.  The column
path reads a columnar group's whole time column through a plan compiled
once from `SourceFormat`: one table lookup and one product turn every
stamp into its minute (a key) and its second, and the standard library
turns each distinct minute into epoch seconds, once.  It stores only what
it can prove `strptime` would give and hands every other row to the row
path, so values and `PARSE_TIME_FAIL` alarms are the row path's by
construction.

The column path's work for a group is ONE native call where the library is
loaded (`native.timestamp_column`: the walk over the stamps, the sums, the
minute memo's lookup and the stores, under the interpreter lock it never
lets go of), and the same plan in few numpy calls where it is not.  Few on
purpose: each numpy call on more than some 500 elements lets go of the
lock, and a worker that lets go of it thirty times a group waits thirty
times for whoever took it.

Without `SourceTimezone` the local zone's offset comes from `time.mktime`
itself, once per distinct minute.  In a local hour that occurs twice (the
end of summer time) `mktime` with `tm_isdst = -1` has two right answers
and glibc picks the one nearer its previous answer: either path's choice
there is an accident of what it parsed before, as it was with one path.
"""

from __future__ import annotations

import calendar
import datetime
import re
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np
from numpy.lib.stride_tricks import as_strided

from .. import native
from ..monitor.alarms import (AlarmLevel, AlarmManager,
                              AlarmType)
from ..models import PipelineEventGroup
from ..pipeline.plugin.interface import PluginContext, Processor
from . import parse_telemetry
from .common import SourceColumns, extract_source, source_spans

#: A group with fewer present rows keeps the row loop.  Measured in this
#: repo's CPU sandbox on the Apache format (my run, PR 28): the column path
#: takes 64-74 us for 32 to 64 rows and 0.19 us a row beyond, the loop
#: 0.76-1.3 us a row behind a warm memo; the two cross at 60 to 110 rows.
COLUMN_MIN_ROWS = 96

#: the fixed-width numeric directives the plan takes: width, the largest
#: first digit strptime takes, and where the number goes — into the minute's
#: key (`_DATE` holds year * 100 + day and, a million-fold, the month;
#: `_HOUR_MINUTE` hour * 100 + minute) or beside it (`_SECOND`)
_DATE, _HOUR_MINUTE, _SECOND, _CHECK = range(4)
_NUMERIC = {"Y": (4, 9, _DATE, 100), "m": (2, 1, _DATE, 1_000_000),
            "d": (2, 3, _DATE, 1), "H": (2, 2, _HOUR_MINUTE, 100),
            "M": (2, 5, _HOUR_MINUTE, 1), "S": (2, 5, _SECOND, 1)}
#: what the table holds for a byte outside its column's class; a proven
#: row's `_CHECK` sum stays far below it, and every sum stays an exact float
_NOT_IN_CLASS = float(1 << 20)
#: the same mark in the native call's byte table: a worth is a digit or an
#: ASCII letter's code, so the top bit is free
_NATIVE_NOT_IN_CLASS = 0xFF
#: seconds of a minute that has none: the date does not exist, the zone has
#: no one offset in it, or the time is before the epoch (the row path stores
#: no negative time)
_NO_SUCH_MINUTE = -(1 << 62)
_EPOCH_ORDINAL = datetime.date(1970, 1, 1).toordinal()
_MEMO_MAX = 4096


def _month_keys() -> Optional[Dict[int, int]]:
    """The locale's abbreviated month names as `time.strptime` reads them,
    three bytes as one number → month; None where a name is not three ASCII
    bytes."""
    keys = {}
    for month in range(1, 13):
        name = time.strftime("%b", (2001, month, 1, 0, 0, 0, 0, 1, 0))
        raw = name.encode("utf-8", "replace")
        try:
            if len(raw) != 3 or max(raw) > 127 \
                    or time.strptime(name, "%b").tm_mon != month:
                return None
        except ValueError:
            return None
        keys[int.from_bytes(raw, "big")] = month
    return keys if len(keys) == 12 else None


class _ColumnPlan:
    """What `SourceFormat` says of every byte of a stamp of `width` bytes.
    `table[column * 256 + byte]` is the byte's worth (a digit's value, a
    month letter's code, 0 for the literal or sign that belongs there) or
    `_NOT_IN_CLASS`; `weights` ([width, 4]) adds the worths up into the
    date, hour * 100 + minute, the second, and the check sum."""

    __slots__ = ("width", "column_base", "table", "weights", "native_table",
                 "native_weights", "months", "month_default", "day_default")

    @classmethod
    def compile(cls, fmt: str) -> Optional["_ColumnPlan"]:
        classes = []        # per column: {byte: worth}
        terms = []          # (column, number, weight)
        seen = set()
        i = 0
        while i < len(fmt):
            ch, d = fmt[i], fmt[i + 1:i + 2]
            if ch != "%" or d == "%":
                if ord(ch) > 127:
                    return None
                i += 1 if ch != "%" else 2
                classes.append({ord(ch): 0})
                continue
            i += 2
            if d in seen:
                return None        # strptime refuses a directive named twice
            seen.add(d)
            if d in _NUMERIC:
                width, first_max, number, weight = _NUMERIC[d]
                for k in reversed(range(width)):
                    terms.append((len(classes), number, weight * 10 ** k))
                    last = first_max if k == width - 1 else 9
                    classes.append({0x30 + v: v for v in range(last + 1)})
            elif d == "b":
                for k in reversed(range(3)):
                    terms.append((len(classes), _DATE, 1_000_000 * 256 ** k))
                    classes.append({b: b for b in range(128)})
            elif d == "z":
                # [+-]HHMM, and nothing that strptime's longer forms of %z
                # (+HH:MM, +HHMMSS, a fraction) could go on into
                if i < len(fmt) and fmt[i] in "%0123456789:.":
                    return None
                classes.append({0x2B: 0, 0x2D: 0})
                for last in (9, 9, 5, 9):                   # minutes 00-59
                    classes.append({0x30 + v: 0 for v in range(last + 1)})
            else:
                return None        # a directive with no one fixed-width form
        if "Y" not in seen or ("b" in seen and "m" in seen):
            return None
        plan = cls()
        plan.width = len(classes)
        plan.column_base = np.arange(plan.width, dtype=np.int32) * 256
        plan.table = np.full(plan.width * 256, _NOT_IN_CLASS)
        for col, worths in enumerate(classes):
            for byte, worth in worths.items():
                plan.table[col * 256 + byte] = worth
        # float64 holds every one of these sums exactly (the largest, a
        # month's three letters a million-fold, is under 2**45), and its
        # product is the fast one
        plan.weights = np.zeros((plan.width, 4))
        plan.weights[:, _CHECK] = 1
        for col, number, weight in terms:
            plan.weights[col, number] = weight
        # the same plan as integers, for `native.timestamp_column` (int64
        # is exact where float64 is: the same sums)
        outside = plan.table == _NOT_IN_CLASS
        plan.native_table = np.where(outside, _NATIVE_NOT_IN_CLASS,
                                     plan.table).astype(np.uint8)
        weights = plan.weights.astype(np.int64)
        plan.native_weights = np.stack(
            [weights[:, _DATE] * 10000 + weights[:, _HOUR_MINUTE],
             weights[:, _SECOND]], axis=1)
        plan.months = None
        if "b" in seen:
            plan.months = _month_keys()
            if plan.months is None:
                return None
        # strptime's month and day where the format names none
        plan.month_default = 0 if seen & {"m", "b"} else 1
        plan.day_default = 0 if "d" in seen else 1
        return plan if plan._agrees_with_strptime(fmt) else None

    def _agrees_with_strptime(self, fmt: str) -> bool:
        """One stamp written under the format, read back by `time.strptime`
        and by the plan: whatever this walk misreads of a format (or a later
        Python reads otherwise) leaves the processor on the row path."""
        try:
            sample = time.strftime(fmt, (2001, 2, 3, 4, 5, 6, 5, 34, 0))
            st = time.strptime(sample, fmt)
        except (ValueError, re.error):
            return False
        raw = np.frombuffer(sample.encode("utf-8"), dtype=np.uint8)
        if len(raw) != self.width:
            return False
        key, second, ok = self.parse(raw, np.zeros(1, dtype=np.int64))
        fields = self.fields(int(key[0]))
        return bool(ok[0]) and fields is not None \
            and fields + (int(second[0]),) == tuple(st[:6]) \
            and self._native_agrees(raw, int(key[0]), int(second[0]))

    def _native_agrees(self, raw: np.ndarray, key: int, second: int) -> bool:
        """The same stamp through the native walk, where the library is
        loaded: the minute's key with an empty memo, then the second beside
        a memo that holds the minute."""
        offsets = np.zeros(1, dtype=np.int32)
        lengths = np.full(1, self.width, dtype=np.int32)
        stored = np.full(1, -1, dtype=np.int64)
        none = np.zeros(0, dtype=np.int64)
        asked = self.native_column(raw, offsets, lengths, None, 0,
                                   (none, none), stored)
        if asked is None:
            return True             # no library: the numpy path is the path
        if asked.missing.tolist() != [key] or asked.stored:
            return False
        memo = (asked.missing.copy(), np.zeros(1, dtype=np.int64))
        found = self.native_column(raw, offsets, lengths, asked.pending, 0,
                                   memo, stored)
        return found.stored == 1 and stored.tolist() == [second]

    def native_column(self, arena: np.ndarray, offsets: np.ndarray,
                      lengths: np.ndarray, rows: Optional[np.ndarray],
                      min_present: int, minutes: Tuple[np.ndarray, np.ndarray],
                      timestamps: np.ndarray
                      ) -> Optional[native.TimestampColumn]:
        """`native.timestamp_column` under this plan; `minutes`: the memo as
        (keys ascending, epoch seconds of each minute's second 0)."""
        return native.timestamp_column(
            arena, offsets, lengths, rows, min_present, self.width,
            self.native_table, self.native_weights, *minutes, timestamps)

    def parse(self, arena: np.ndarray, offsets: np.ndarray
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """For the `width` bytes at each offset: the key of the stamp's
        minute (for `fields`), its second, and whether every byte is in its
        column's class.  A row that is not has numbers that mean nothing."""
        stamps = as_strided(arena, (len(arena) - self.width + 1, self.width),
                            arena.strides * 2, writeable=False)[offsets]
        sums = (self.table.take(stamps + self.column_base) @ self.weights
                ).astype(np.int64)
        return (sums[:, _DATE] * 10000 + sums[:, _HOUR_MINUTE],
                sums[:, _SECOND], sums[:, _CHECK] < int(_NOT_IN_CLASS))

    def fields(self, key: int) -> Optional[Tuple[int, int, int, int, int]]:
        """Year, month, day, hour and minute of a minute's key; None for a
        month name that is none.  Whether such a date exists is not asked
        here."""
        date, hour_minute = divmod(key, 10000)
        month, year_day = divmod(date, 1_000_000)
        if self.months is not None:
            month = self.months.get(month)
            if month is None:
                return None
        year, day = divmod(year_day, 100)
        return (year, month + self.month_default, day + self.day_default,
                *divmod(hour_minute, 100))


class ProcessorParseTimestamp(Processor):
    name = "processor_parse_timestamp_native"
    supports_columnar = True

    def __init__(self) -> None:
        super().__init__()
        self.source_key = b"time"
        self.source_format = "%Y-%m-%d %H:%M:%S"
        self.source_timezone_offset = None  # seconds east of UTC, None=local
        self._pipeline = ""
        self._plan: Optional[_ColumnPlan] = None
        #: the row path's: distinct value → epoch seconds (−1: no parse)
        self._memo: Dict[bytes, int] = {}
        #: the column path's: the plan's key of a minute → epoch seconds of
        #: its second 0 (`_NO_SUCH_MINUTE`: none)
        self._minute_memo: Dict[int, int] = {}
        #: the same for the native call: (keys ascending, their seconds),
        #: replaced as a pair whenever it grows
        self._minutes = (np.zeros(0, dtype=np.int64),) * 2

    def init(self, config: Dict[str, Any], context: PluginContext) -> bool:
        super().init(config, context)
        self.source_key = config.get("SourceKey", "time").encode()
        self.source_format = config.get("SourceFormat", "%Y-%m-%d %H:%M:%S")
        tz = config.get("SourceTimezone")  # e.g. "GMT+08:00"
        if tz:
            sign = 1 if "+" in tz else -1
            hh_mm = tz.split("+")[-1].split("-")[-1]
            try:
                hh, mm = hh_mm.split(":")
                self.source_timezone_offset = sign * (int(hh) * 3600 + int(mm) * 60)
            except ValueError:
                self.source_timezone_offset = None
        self._pipeline = getattr(context, "pipeline_name", "") or ""
        self._plan = _ColumnPlan.compile(self.source_format)
        return True

    def _alarm_fail(self) -> None:
        AlarmManager.instance().send_alarm(
            AlarmType.PARSE_TIME_FAIL,
            f"timestamp parse failed (format {self.source_format!r})",
            AlarmLevel.WARNING)

    def _parse_one(self, data: bytes) -> int:
        ts = self._memo.get(data)
        if ts is not None:
            if ts < 0:
                # memoized FAILURE: still alarm, or the aggregated count
                # undercounts a stream of identical bad values by memo-hits
                self._alarm_fail()
            return ts
        try:
            st = time.strptime(data.decode("utf-8", "replace"), self.source_format)
            if self.source_timezone_offset is not None:
                ts = int(calendar.timegm(st)) - self.source_timezone_offset
            else:
                ts = int(time.mktime(st))
        except ValueError:
            ts = -1
            self._alarm_fail()
        if len(self._memo) > _MEMO_MAX:
            self._memo.clear()
        self._memo[data] = ts
        return ts

    def _parse_rows(self, arena: np.ndarray, offsets: np.ndarray,
                    lengths: np.ndarray, tss: np.ndarray,
                    rows: np.ndarray) -> None:
        for i in rows.tolist():
            o = int(offsets[i])
            ts = self._parse_one(arena[o : o + int(lengths[i])].tobytes())
            if ts >= 0:
                tss[i] = ts

    def _minute_seconds(self, key: int) -> int:
        """Epoch seconds of second 0 of the minute with this key of the
        plan's: what the row path gives, asked of the standard library once
        a minute; `_NO_SUCH_MINUTE` where it has no one answer."""
        seconds = self._minute_memo.get(key)
        if seconds is not None:
            return seconds
        seconds = _NO_SUCH_MINUTE
        fields = self._plan.fields(key)
        try:
            if fields is not None and fields[3] <= 23:
                year, month, day, hour, minute = fields
                date = datetime.date(year, month, day)
                if self.source_timezone_offset is not None:
                    # calendar.timegm's sum, less the zone
                    first = (((date.toordinal() - _EPOCH_ORDINAL) * 24 + hour)
                             * 60 + minute) * 60 - self.source_timezone_offset
                    last = first + 59
                else:
                    # the row path's call (mktime reads no weekday and no
                    # day of the year), for the minute's two ends
                    first = int(time.mktime(fields + (0, 0, 1, -1)))
                    last = int(time.mktime(fields + (59, 0, 1, -1)))
                # a zone that changes its offset inside the minute has no
                # one answer for it
                if first >= 0 and last - first == 59:
                    seconds = first
        except (ValueError, OverflowError):
            pass                # no such date, or none that mktime takes
        if len(self._minute_memo) > _MEMO_MAX:
            self._minute_memo.clear()
        self._minute_memo[key] = seconds
        return seconds

    def _parse_column(self, src: SourceColumns, tss: np.ndarray,
                      present: np.ndarray) -> None:
        """`present`: the rows that have the field.  Those the plan proves
        are stored at once, the others go through the row path."""
        plan = self._plan
        rows = present[src.lengths[present] == plan.width]
        rest = src.present.copy()
        try:
            key, second, ok = plan.parse(src.arena, src.offsets[rows])
        except (IndexError, ValueError):
            pass                # a span that leaves the arena: no row proven
        else:
            minutes = np.unique(key[ok])
            if len(minutes):
                seconds = np.array([self._minute_seconds(k)
                                    for k in minutes.tolist()])
                # a row not proven may find no minute: clip, it is not stored
                ts = np.take(seconds, np.searchsorted(minutes, key),
                             mode="clip") + second
                ok &= ts >= 0
                proven = rows[ok]
                tss[proven] = ts[ok]
                rest[proven] = False
        rest = np.flatnonzero(rest)
        self._parse_rows(src.arena, src.offsets, src.lengths, tss, rest)
        parse_telemetry.note_rows(self.name, self._pipeline,
                                  len(present), len(rest))

    def _parse_column_native(self, group: PipelineEventGroup) -> bool:
        """The column path as one native call a group, two for a group that
        brings a minute the memo lacks: the call walks the field's columns as
        the group stores them, stores what the plan proves and names the rows
        for the row path.  False, and nothing done, where the call does not
        apply: no library, a group under `COLUMN_MIN_ROWS` present rows."""
        cols = group.columns
        spans = source_spans(cols, self.source_key)
        if spans is None:
            return False
        offsets, lengths, _ = spans
        arena = group.source_buffer.as_array()
        tss = cols.timestamps
        found = self._plan.native_column(arena, offsets, lengths, None,
                                         COLUMN_MIN_ROWS, self._minutes, tss)
        if found is None:
            return False
        present, stored, rest, calls = found.present, found.stored, \
            found.rest, 1
        while len(found.pending):
            # the standard library once a minute, as on the numpy path
            seconds = np.array([self._minute_seconds(k)
                                for k in found.missing.tolist()])
            found = self._plan.native_column(
                arena, offsets, lengths, found.pending, 0,
                self._grow_minutes(found.missing, seconds), tss)
            stored += found.stored
            rest = np.sort(np.concatenate([rest, found.rest]))
            calls += 1
        self._parse_rows(arena, offsets, lengths, tss, rest)
        parse_telemetry.note_rows(self.name, self._pipeline, present,
                                  len(rest), native_rows=stored,
                                  native_calls=calls)
        return True

    def _grow_minutes(self, keys: np.ndarray, seconds: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """The native call's memo with these minutes in it; past `_MEMO_MAX`
        it starts over from them, as `_minute_memo` does."""
        known = self._minutes
        if len(known[0]) + len(keys) > _MEMO_MAX:
            known = (keys.copy(), seconds)
        else:
            keys = np.concatenate([known[0], keys])
            order = np.argsort(keys, kind="stable")
            known = (keys[order], np.concatenate([known[1], seconds])[order])
        self._minutes = known
        return known

    def process(self, group: PipelineEventGroup) -> None:
        if self._plan is not None and group.columns is not None \
                and not group._events \
                and len(group.columns) >= COLUMN_MIN_ROWS \
                and self._parse_column_native(group):
            return
        src = extract_source(group, self.source_key)
        if src is None:
            return
        if src.columnar:
            tss = group.columns.timestamps
            present = np.flatnonzero(src.present)
            if self._plan is None or len(present) < COLUMN_MIN_ROWS:
                self._parse_rows(src.arena, src.offsets, src.lengths, tss,
                                 present)
            else:
                self._parse_column(src, tss, present)
            return
        for ev in group.events:
            if not hasattr(ev, "get_content"):
                continue
            v = ev.get_content(self.source_key)
            if v is None:
                continue
            ts = self._parse_one(v.to_bytes())
            if ts >= 0:
                ev.timestamp = ts

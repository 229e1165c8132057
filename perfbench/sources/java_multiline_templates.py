"""Line source ``java_multiline_templates``: fixed-width Java stack-trace
records, one record a unit.

``BASELINE.json`` config 2 ("Multi-line Java stacktrace": the reader's
``Multiline.StartPattern`` merges physical lines into records, a regex parses
the record).  Upstream's README states "multi-line" and no generator, so the
record is stated here (the configuration lists every item under ``assumed``):
``line_bytes`` bytes with the final newline, holding some two dozen physical
lines —

    2026-03-17 08:15:42 ERROR [exec-17] com.acme.….OrderService - req=<12 digits> <exception class>: <message>
    \\tat com.acme.orders.OrderService.submit(OrderService.java:214)
    ...
    Caused by: java.sql.SQLTransientConnectionException: <message>     (cause_share of the templates)
    \\tat ...
    \\t... 17 more

— Logback's usual pattern without milliseconds, so that the quick-start regex
``(\\d{4}-\\d{2}-\\d{2} \\d{2}:\\d{2}:\\d{2}) (\\w+) ([\\s\\S]*)`` parses it.  The
harness's unit is a fixed-width "line" that maps to at most one sink record
(``generator.py`` ``line_bytes``, ``check.py`` ``expected(line)``); here one
unit is one record, embedded newlines and all.  The logger name takes up what
level and thread leave of the head, so the 12-digit sequence number sits in
the same columns of every record, and the last frame takes up what the others
leave of the width.  Frames are 40–140 bytes, every physical line is under
256, everything is ASCII.

Template classes (shares of the pool; largest remainders):

    level_mix     {level: weight}: the parsable templates take their level in
                  these proportions
    cause_share   templates with one ``Caused by:`` line, its own frames and a
                  ``\\t... NN more`` line
    blank_share   templates with one empty physical line inside the trace
    reject_share  templates the parse regex rejects while their head line
                  still matches the StartPattern (two kinds in turn: the
                  seconds missing from the time; a ``-`` inside the level)

No unit lacks a start line and no continuation line matches the
StartPattern: either would change a neighbouring unit's record, which a
per-unit reference cannot express.

Record ``j`` takes template ``mix(seed, j) % pool`` (the arithmetic of
``apache_templates``): a pure function of (seed, j).
"""

from __future__ import annotations

import random
import re

import numpy as np

from benchlib import spec

_apache = spec.load_module("sources", "apache_templates")
SEQ_DIGITS = _apache.SEQ_DIGITS
_SEQ_RX = re.compile(rb"req=(\d{%d})" % SEQ_DIGITS)
_POW10 = 10 ** np.arange(SEQ_DIGITS - 1, -1, -1, dtype=np.int64)
#: bytes of a head line from its first byte to the first sequence digit
_HEAD_BYTES = 96
_MAX_LINE = 255              # a physical line without its newline
_LOWER = "abcdefghijklmnopqrstuvwxyz"
_CLASSES = ("Dao", "Task", "Handler", "Filter", "Invoker", "Executor",
            "OrderService", "PaymentClient", "SessionStore", "RetryTemplate",
            "ConnectionPool", "DispatcherServlet", "TransactionInterceptor")
_METHODS = ("run", "get", "call", "invoke", "submit", "process", "doFilter",
            "execute", "proceed", "handleRequest", "invokeWithinTransaction")
_EXCEPTIONS = ("java.lang.IllegalStateException",
               "java.lang.NullPointerException",
               "java.util.concurrent.TimeoutException",
               "java.io.IOException",
               "org.springframework.dao.DataAccessResourceFailureException",
               "com.acme.orders.OrderRejectedException")
_CAUSES = ("java.sql.SQLTransientConnectionException",
           "java.net.SocketTimeoutException",
           "java.net.ConnectException",
           "io.netty.channel.ConnectTimeoutException")
_WORDS = ("request", "failed", "upstream", "timeout", "after", "retries",
          "connection", "reset", "by", "peer", "pool", "exhausted", "order",
          "rejected", "while", "waiting", "for", "lock", "shard", "leader",
          "not", "available", "payload", "too", "large", "state", "invalid")


def _dotted(r: random.Random, n: int) -> str:
    """A package path of exactly ``n`` bytes: lowercase segments and dots."""
    out = []
    run = 0
    for i in range(n):
        last = i == n - 1
        if run >= 3 and not last and i < n - 2 and r.randrange(6) == 0:
            out.append(".")
            run = 0
        else:
            out.append(r.choice(_LOWER))
            run += 1
    return "".join(out)


def _words(r: random.Random, lo: int, hi: int) -> str:
    n = r.randrange(lo, hi + 1)
    out = r.choice(_WORDS)
    while len(out) < n:
        out += " " + r.choice(_WORDS)
    return out[:n].rstrip() or "x"


def _frame(r: random.Random, n: int) -> str:
    """One ``\\tat pkg.Class.method(Class.java:N)`` line of exactly ``n``
    bytes with its newline; the package takes up the slack."""
    for _ in range(64):
        cls, method = r.choice(_CLASSES), r.choice(_METHODS)
        num = str(r.randrange(10, 2000))
        fixed = len(f"\tat .{cls}.{method}({cls}.java:{num})\n")
        if n - fixed >= 3:
            break
    else:
        cls, method, num = "Dao", "run", "17"
        fixed = len(f"\tat .{cls}.{method}({cls}.java:{num})\n")
    if n - fixed < 3 or n - 1 > _MAX_LINE:
        raise ValueError(f"no frame of {n} bytes")
    return f"\tat {_dotted(r, n - fixed)}.{cls}.{method}({cls}.java:{num})\n"


class JavaMultilineTemplates:
    def __init__(self, params: dict, seed: int):
        self.seed = int(seed)
        self.line_bytes = int(params["line_bytes"])
        self.pool = int(params["pool"])
        r = random.Random(self.seed)
        self.kinds = _kinds(params, self.pool, r)
        rows = [np.frombuffer(self._template(r, kind), np.uint8)
                for kind in self.kinds]
        self.templates = np.stack(rows)                  # [pool, line_bytes]
        self.seq_offset = _HEAD_BYTES

    def _head(self, r: random.Random, kind: dict) -> str:
        level = kind["level"]
        stamp = (f"2026-03-{r.randrange(1, 29):02d} {r.randrange(24):02d}:"
                 f"{r.randrange(60):02d}:{r.randrange(60):02d}")
        if kind.get("reject") == "seconds":
            stamp = stamp[:-3]
        elif kind.get("reject") == "level":
            level = level[:2] + "-" + level[2:]
        thread = f"exec-{r.randrange(1, 200)}"
        front = f"{stamp} {level} [{thread}] "
        back = f".{r.choice(_CLASSES)} - req="
        # the logger's package takes up what stamp, level and thread leave,
        # so the sequence digits sit in the same columns of every record
        head = front + _dotted(r, _HEAD_BYTES - len(front) - len(back)) + back
        if len(head) != _HEAD_BYTES:
            raise ValueError("the head does not end at the sequence columns")
        return (head + "0" * SEQ_DIGITS + f" {r.choice(_EXCEPTIONS)}: "
                + _words(r, 20, 60) + "\n")

    def _template(self, r: random.Random, kind: dict) -> bytes:
        lines = [self._head(r, kind)]
        room = self.line_bytes - len(lines[0])
        # what is inserted among the frames, each at a frame boundary
        extras = []
        if kind.get("cause"):
            extras.append(f"Caused by: {r.choice(_CAUSES)}: "
                          + _words(r, 20, 70) + "\n")
            extras.append(f"\t... {r.randrange(3, 60)} more\n")
        if kind.get("blank"):
            extras.append("\n")
        room -= sum(len(e) for e in extras)
        frames = []
        while room > 240:
            n = r.randrange(40, 141)
            frames.append(_frame(r, n))
            room -= n
        frames.append(_frame(r, room))       # the last frame: the slack
        body = frames[:]
        if kind.get("cause"):
            at = r.randrange(2, max(3, len(frames) - 3))
            body = frames[:at] + [extras[0]] + frames[at:] + [extras[1]]
        if kind.get("blank"):
            body.insert(r.randrange(1, len(body)), "\n")
        rec = "".join(lines + body).encode("ascii")
        if len(rec) != self.line_bytes or not rec.endswith(b"\n"):
            raise ValueError("template does not fill the record width")
        if max(len(ln) for ln in rec.split(b"\n")) > _MAX_LINE:
            raise ValueError("a physical line is over 255 bytes")
        return rec

    def template_of(self, first: int, n: int) -> np.ndarray:
        j = np.arange(first, first + n, dtype=np.int64)
        return (_apache._mix(self.seed, j) % np.uint64(self.pool)) \
            .astype(np.int64)

    def block(self, first: int, n: int) -> np.ndarray:
        """Records ``first .. first+n`` as a [n, line_bytes] uint8 array."""
        return self.block_at(np.arange(first, first + n, dtype=np.int64))

    def block_at(self, j: np.ndarray) -> np.ndarray:
        """The records numbered ``j`` (any order, any gaps), one row each."""
        j = np.asarray(j, np.int64)
        t = (_apache._mix(self.seed, j) % np.uint64(self.pool)) \
            .astype(np.intp)
        rows = np.take(self.templates, t, axis=0)
        rows[:, self.seq_offset:self.seq_offset + SEQ_DIGITS] = \
            (j[:, None] // _POW10) % 10 + 48
        return rows

    def line(self, j: int) -> bytes:
        return self.block(j, 1).tobytes()

    @staticmethod
    def seqs_in(records: bytes) -> np.ndarray:
        """The sequence number of every sink record in ``records`` (whole
        lines of the sink), in order."""
        found = _SEQ_RX.findall(records)
        if not found:
            return np.empty(0, np.int64)
        return np.array(found, dtype=f"S{SEQ_DIGITS}").astype(np.int64)


def _kinds(params: dict, pool: int, r: random.Random) -> list:
    """One dict a template: its level, and whether it holds a cause, a
    blank line, a reject (which kind, in turn)."""
    n_reject = round(pool * float(params["reject_share"]))
    levels = _apache.apportion(params["level_mix"], pool - n_reject)
    r.shuffle(levels)
    kinds = [{"level": lv} for lv in levels]
    for share, key in ((float(params["cause_share"]), "cause"),
                       (float(params["blank_share"]), "blank")):
        for k in r.sample(range(len(kinds)), round(len(kinds) * share)):
            kinds[k][key] = True
    for k in range(n_reject):
        kinds.append({"level": r.choice(sorted(params["level_mix"])),
                      "reject": ("seconds", "level")[k % 2],
                      "cause": r.random() < float(params["cause_share"])})
    r.shuffle(kinds)
    return kinds


def make(params: dict, seed: int) -> JavaMultilineTemplates:
    return JavaMultilineTemplates(params, seed)

"""Line source ``apache_templates``: fixed-width Apache access-log lines.

Upstream's regression shape (``performance_file_to_blackhole_*``: 512-byte
lines, regex scenario) in the Apache common log format.  What a published
access log does not carry at that width is stated, not guessed: the status
codes are drawn in the shares of a published corpus (``status_mix``, counts
per status; the configuration names the corpus), and the bytes that widen a
line to ``line_bytes`` sit in the request's query string, where access-log
lines of that length have them (upstream's own documented sample line is a
``POST /PutData?Category=...&Signature=...``), not in a padded capture.
Every line carries its sequence number inside the captured ``url``
(``/api/v1/resource/<12 digits>?...``) so that a sink record names its line.

Lines are drawn from a pool of ``pool`` templates built from the seed; line
``j`` takes template ``mix(seed, j) % pool`` — a pure function of (seed, j),
so the generator, the tailer and the comparison each compute any range of the
stream on their own, in bulk with numpy, and never ship lines to one another.

Parameters (the ``source`` object of a configuration's ``config.json``):

    line_bytes    bytes per line, newline included
    pool          templates in the pool
    reject_share  share of templates the pattern rejects (three kinds)
    status_mix    {status: count}: the other templates take their status in
                  these proportions (largest remainders; a status rarer than
                  one template in the pool gets none)
"""

from __future__ import annotations

import random
import re

import numpy as np

SEQ_DIGITS = 12
_ANCHOR = b"/api/v1/resource/"
_SEQ_RX = re.compile(rb"/api/v1/resource/(\d{%d})" % SEQ_DIGITS)
_METHODS = ("GET", "POST", "PUT", "HEAD")
_QUERY_KEYS = ("id", "q", "ref", "sid", "page", "token", "lang", "ts")
_QUERY_CHARS = "abcdefghijklmnopqrstuvwxyz0123456789"
_HEAD_VARIABLE = 28          # ip + user + method, in bytes
_POW10 = 10 ** np.arange(SEQ_DIGITS - 1, -1, -1, dtype=np.int64)


def _mix(seed: int, j: np.ndarray) -> np.ndarray:
    """splitmix64 of (seed, j), vectorised; uint64 arithmetic wraps."""
    with np.errstate(over="ignore"):
        z = j.astype(np.uint64) + np.uint64(seed % (1 << 63)) * np.uint64(0x9E3779B97F4A7C15)
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


class ApacheTemplates:
    def __init__(self, params: dict, seed: int):
        self.seed = int(seed)
        self.line_bytes = int(params["line_bytes"])
        self.pool = int(params["pool"])
        self.statuses = {str(k) for k in params["status_mix"]}
        n_reject = round(self.pool * float(params["reject_share"]))
        r = random.Random(self.seed)
        kinds = ["reject"] * n_reject + apportion(
            params["status_mix"], self.pool - n_reject)
        r.shuffle(kinds)
        rows, offs = [], []
        for k, kind in enumerate(kinds):
            line, off = self._template(r, k, kind)
            rows.append(np.frombuffer(line, np.uint8))
            offs.append(off)
        self.templates = np.stack(rows)                  # [pool, line_bytes]
        if len(set(offs)) != 1:
            raise ValueError("templates disagree on the sequence columns")
        self.seq_offset = offs[0]                        # where the digits go

    def _template(self, r: random.Random, k: int, kind: str):
        status = r.choice(sorted(self.statuses)) if kind == "reject" else kind
        ip = f"10.{r.randrange(256)}.{(k >> 8) & 255}.{k & 255}"
        method = r.choice(_METHODS)
        # the user name takes up what ip and method leave of a fixed head, so
        # the sequence digits sit in the same columns of every line
        user = "u" + "".join(r.choice("0123456789") for _ in range(
            _HEAD_VARIABLE - len(ip) - len(method) - 1))
        head = (f"{ip} - {user} "
                f"[10/Oct/2000:13:{r.randrange(60):02d}:{r.randrange(60):02d} -0700] "
                f'"{method} ')
        off = len(head) + len(_ANCHOR)
        tail = f' HTTP/1.1" {status} {r.randrange(100, 1000000)}'
        reject = r.randrange(3) if kind == "reject" else None
        if reject == 0:                         # no opening bracket
            head = head.replace("[", "(", 1)
        elif reject == 1:                       # two-digit status
            tail = f' HTTP/1.1" {status[:2]} {r.randrange(100, 1000000)}'
        elif reject == 2:                       # a non-digit ends the size
            tail = tail[:-1] + "x"
        room = self.line_bytes - 1 - len(head) - len(_ANCHOR) - SEQ_DIGITS \
            - len(tail)
        line = (head + _ANCHOR.decode() + "0" * SEQ_DIGITS
                + _query(r, room) + tail).encode("ascii") + b"\n"
        if len(line) != self.line_bytes:
            raise ValueError("template does not fill the line width")
        return line, off

    def template_of(self, first: int, n: int) -> np.ndarray:
        j = np.arange(first, first + n, dtype=np.int64)
        return (_mix(self.seed, j) % np.uint64(self.pool)).astype(np.int64)

    def block(self, first: int, n: int) -> np.ndarray:
        """Lines ``first .. first+n`` as a [n, line_bytes] uint8 array."""
        return self.block_at(np.arange(first, first + n, dtype=np.int64))

    def block_at(self, j: np.ndarray) -> np.ndarray:
        """The lines numbered ``j`` (any order, any gaps), one row each."""
        j = np.asarray(j, np.int64)
        t = (_mix(self.seed, j) % np.uint64(self.pool)).astype(np.intp)
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


def apportion(weights: dict, seats: int) -> list:
    """``seats`` names in the proportions of ``weights`` (largest
    remainders), as a list."""
    total = float(sum(weights.values()))
    exact = {k: seats * w / total for k, w in weights.items()}
    got = {k: int(x) for k, x in exact.items()}
    by_remainder = sorted(exact, key=lambda k: (got[k] - exact[k], str(k)))
    for k in by_remainder[:seats - sum(got.values())]:
        got[k] += 1
    return [str(k) for k in sorted(got, key=str) for _ in range(got[k])]


def _query(r: random.Random, room: int) -> str:
    """A query string of exactly ``room`` bytes: ``?key=value&key=value``."""
    if room < 4:
        raise ValueError("the line width leaves no room for a query string")
    out = "?"
    while len(out) < room:
        out += r.choice(_QUERY_KEYS) + "=" + "".join(
            r.choice(_QUERY_CHARS) for _ in range(r.randrange(6, 33))) + "&"
    out = out[:room]
    # never end on a separator: the last byte is a value's
    return out[:-1] + "0" if out[-1] in "&=?" else out


def make(params: dict, seed: int) -> ApacheTemplates:
    return ApacheTemplates(params, seed)

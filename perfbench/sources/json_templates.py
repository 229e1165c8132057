"""Line source ``json_templates``: fixed-width JSON application-log lines.

``BASELINE.json`` config 4 ("JSON parse + regex field filter on 1 KB
structured events"; upstream's regression harness feeds JSON lines through a
JSON parse and a filter on a parsed key).  Upstream's generator states
nothing beyond "JSON", so the shape is stated here, not guessed in silence
(the configuration lists it under ``assumed``): one object a line, 14
top-level keys in a fixed order —

    time level service host pid seq trace_id method path status latency_ms
    ctx tags msg

— strings, numbers (``pid``, ``status``, ``latency_ms``), a nested object two
deep (``ctx``: thread, logger, sampled, parent and ``req`` with its own
members, a boolean and a ``null`` among them), an array (``tags``), written
without optional whitespace.  ``ctx`` and ``msg`` carry the width, as
application logs carry it.  ``seq`` is the line's 12-digit sequence number (a
string: a number may not lead with zeros), in the same columns of every line:
``host`` takes up what the other members before it leave.

Template classes (shares of the pool; largest remainders):

    level_mix        {level: weight}: the parsable templates take their level
                     in these proportions
    escape_share     of the kept and of the dropped templates alike: one
                     string value holds an escape (``\\"``, ``\\\\``, ``\\n``,
                     ``\\u00e9``), so its decoded bytes differ from its span
    extra_key_share  likewise: one more member (``retry``, before ``ctx``)
    missing_key_share likewise: ``method`` is absent
    reject_share     of the pool: lines that do not parse — cut short inside
                     ``msg``, a bracket too many in ``ctx``, the object
                     wrapped in a bare array

``keep_levels`` names the levels the deployment's filter keeps, only so that
the classes can be spread over kept and dropped templates alike; what a line's
fate is, the plain reference decides from the line.

Line ``j`` takes template ``mix(seed, j) % pool`` (the arithmetic of
``apache_templates``): a pure function of (seed, j).

``needs_of_program`` states what the checkout under test has to hold for the
deployment to be this one — ``file`` (under the checkout's root) and a text it
``holds`` — and ``make`` refuses, with a ``SpecError`` and before anything is
built or started, a checkout that lacks it.  A program whose
``processor_parse_json_tpu`` publishes no fused stage does run the pipeline,
on the host's native plane and with no device operation: another deployment,
which this cell does not measure.  The text is read, nothing of the program
is imported.
"""

from __future__ import annotations

import os
import random
import re

import numpy as np

from benchlib import spec

_apache = spec.load_module("sources", "apache_templates")
SEQ_DIGITS = _apache.SEQ_DIGITS
KEYS = ("time", "level", "service", "host", "pid", "seq", "trace_id", "method",
        "path", "status", "latency_ms", "ctx", "tags", "msg")
_SEQ_RX = re.compile(rb'"seq": "(\d{%d})"' % SEQ_DIGITS)
_HEAD_BYTES = 132            # from '{' to the first sequence digit
_SERVICES = ("orders", "billing", "auth", "search", "gateway", "inventory")
_METHODS = ("GET", "POST", "PUT", "DELETE")
_LOGGERS = ("com.acme.orders.OrderService", "com.acme.http.AccessFilter",
            "com.acme.db.ConnectionPool", "com.acme.cache.RegionClient")
_WORDS = ("request", "completed", "upstream", "timeout", "retrying", "cache",
          "miss", "user", "session", "expired", "connection", "reset", "peer",
          "payload", "validated", "queue", "depth", "threshold", "exceeded",
          "shard", "rebalanced", "leader", "elected", "snapshot", "written")
_ESCAPES = ('user \\"alice\\" denied', 'path C:\\\\srv\\\\app.log',
            'caf\\u00e9 order', 'line one\\nline two')
_STATUS = (200, 200, 200, 201, 204, 302, 400, 404, 500, 503)
_HEX = "0123456789abcdef"


class JsonTemplates:
    def __init__(self, params: dict, seed: int):
        self.seed = int(seed)
        self.line_bytes = int(params["line_bytes"])
        self.pool = int(params["pool"])
        r = random.Random(self.seed)
        self.kinds = _kinds(params, self.pool, r)
        rows = [np.frombuffer(self._template(r, k, kind), np.uint8)
                for k, kind in enumerate(self.kinds)]
        self.templates = np.stack(rows)                  # [pool, line_bytes]
        self.seq_offset = _HEAD_BYTES

    def _template(self, r: random.Random, k: int, kind: dict) -> bytes:
        level = kind["level"]
        reject = kind.get("reject")
        opener = '[{' if reject == "array" else '{'
        head = (f'{opener}"time":"2026-03-{r.randrange(1, 29):02d}T'
                f'{r.randrange(24):02d}:{r.randrange(60):02d}:'
                f'{r.randrange(60):02d}.{r.randrange(1000):03d}Z",'
                f'"level":"{level}","service":"{r.choice(_SERVICES)}",'
                f'"host":"')
        tail_of_head = f'","pid":{r.randrange(1000, 65536)},"seq":"'
        # the host name takes up what the members before the sequence number
        # leave, so its digits sit in the same columns of every line
        room = _HEAD_BYTES - len(head) - len(tail_of_head)
        host = f"node-{k:04d}-" + "".join(
            r.choice(_HEX) for _ in range(room - 10))
        head += host + tail_of_head
        if len(head) != _HEAD_BYTES:
            raise ValueError("the head does not end at the sequence columns")
        members = [f'"trace_id":"{_hex(r, 32)}"']
        if not kind.get("missing"):
            members.append(f'"method":"{r.choice(_METHODS)}"')
        path = f'/api/v{r.randrange(1, 4)}/{r.choice(_SERVICES)}/' \
               f'{r.randrange(100000)}'
        escape = kind.get("escape")
        if escape == len(_ESCAPES):
            path += '?q=\\"x\\"'
        members += [f'"path":"{path}"', f'"status":{r.choice(_STATUS)}',
                    # two decimals, the last never 0: the token is the
                    # number's canonical text
                    f'"latency_ms":{r.randrange(1, 5000)}.'
                    f'{r.randrange(10)}{r.randrange(1, 10)}']
        if kind.get("extra"):
            members.append(f'"retry":{r.randrange(1, 4)}')
        ctx = (f'"ctx":{{"thread":"worker-{r.randrange(64)}",'
               f'"logger":"{r.choice(_LOGGERS)}",'
               f'"sampled":{r.choice(("true", "false"))},"parent":null,'
               f'"req":{{"id":"{_hex(r, 16)}","attempt":{r.randrange(1, 4)},'
               f'"bytes":{r.randrange(100, 100000)},'
               f'"peer":"10.{r.randrange(256)}.{r.randrange(256)}.'
               f'{r.randrange(256)}","flags":["{r.choice(_WORDS)}",'
               f'{r.randrange(10)}]}}}}')
        if reject == "unbalanced":
            ctx = ctx[:-1] + "]}"
        members.append(ctx)
        members.append('"tags":["' + '","'.join(
            r.sample(_WORDS, 3)) + f'",{r.randrange(100)}]')
        body = '",' + ",".join(members)
        closer = '"}]' if reject == "array" else '"}'
        start = head + "0" * SEQ_DIGITS + body + ',"msg":"'
        room = self.line_bytes - 1 - len(start) - len(closer)
        if reject == "truncated":               # cut short inside msg
            room, closer = room + len(closer), ""
        msg = _message(r, room, _ESCAPES[escape]
                       if escape is not None and escape < len(_ESCAPES)
                       else "")
        line = (start + msg + closer).encode("ascii") + b"\n"
        if len(line) != self.line_bytes:
            raise ValueError("template does not fill the line width")
        return line

    def template_of(self, first: int, n: int) -> np.ndarray:
        j = np.arange(first, first + n, dtype=np.int64)
        return (_apache._mix(self.seed, j) % np.uint64(self.pool)) \
            .astype(np.int64)

    def block(self, first: int, n: int) -> np.ndarray:
        """Lines ``first .. first+n`` as a [n, line_bytes] uint8 array."""
        return self.block_at(np.arange(first, first + n, dtype=np.int64))

    def block_at(self, j: np.ndarray) -> np.ndarray:
        """The lines numbered ``j`` (any order, any gaps), one row each."""
        j = np.asarray(j, np.int64)
        t = (_apache._mix(self.seed, j) % np.uint64(self.pool)) \
            .astype(np.intp)
        rows = np.take(self.templates, t, axis=0)
        rows[:, self.seq_offset:self.seq_offset + SEQ_DIGITS] = \
            (j[:, None] // _apache._POW10) % 10 + 48
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
    """One dict per template: its level and the classes it belongs to."""
    n_reject = round(pool * float(params["reject_share"]))
    levels = _apache.apportion(params["level_mix"], pool - n_reject)
    keep = set(params["keep_levels"])
    kinds = [{"level": lv} for lv in levels]
    for part in ([k for k in kinds if k["level"] in keep],
                 [k for k in kinds if k["level"] not in keep]):
        r.shuffle(part)
        at = 0
        for cls, share in (("escape", params["escape_share"]),
                           ("extra", params["extra_key_share"]),
                           ("missing", params["missing_key_share"])):
            n = round(len(part) * float(share))
            for i, k in enumerate(part[at:at + n]):
                # the escapes in turn (the four phrases in msg, then the one
                # in path), so that every seed has as many of each, among the
                # kept lines too: a decoded non-ASCII byte is what a JSON
                # serializer treats apart
                k[cls] = i % (len(_ESCAPES) + 1) if cls == "escape" else True
            at += n
    for i in range(n_reject):
        kinds.append({"level": r.choice(sorted(params["level_mix"])),
                      "reject": ("truncated", "unbalanced", "array")[i % 3]})
    r.shuffle(kinds)
    return kinds


def _hex(r: random.Random, n: int) -> str:
    return "".join(r.choice(_HEX) for _ in range(n))


def _message(r: random.Random, room: int, phrase: str) -> str:
    """A message of exactly ``room`` bytes: words, behind ``phrase`` (one
    that holds an escape) where there is one."""
    if room < 48:
        raise ValueError("the line width leaves no room for a message")
    out = phrase + " " if phrase else ""
    while len(out) < room:
        out += r.choice(_WORDS) + " "
    out = out[:room]
    return out[:-1] + "." if out[-1] in " \\" else out


def class_counts(source: JsonTemplates) -> dict:
    """Templates by class, as the configuration states them."""
    out = {"pool": source.pool, "reject": 0, "escape": 0, "extra": 0,
           "missing": 0, "levels": {}}
    for k in source.kinds:
        if "reject" in k:
            out["reject"] += 1
            continue
        out["levels"][k["level"]] = out["levels"].get(k["level"], 0) + 1
        for cls in ("escape", "extra", "missing"):
            out[cls] += cls in k
    return out


def hold_program_to(needs: dict, root: str = spec.ROOT) -> None:
    """Raise ``SpecError`` unless ``root``'s ``needs['file']`` holds the text
    ``needs['holds']``."""
    path = os.path.join(root, needs["file"])
    try:
        with open(path) as f:
            found = needs["holds"] in f.read()
    except OSError:
        found = False
    if not found:
        raise spec.SpecError(
            f"{root} cannot run this configuration: {needs['file']} holds no "
            f"{needs['holds']!r} ({needs['why']})")


def make(params: dict, seed: int) -> JsonTemplates:
    hold_program_to(params["needs_of_program"])
    return JsonTemplates(params, seed)

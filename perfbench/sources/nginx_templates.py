"""Line source ``nginx_templates``: fixed-width nginx access-log lines for a
grok ``Match`` list with fall-backs.

``BASELINE.json`` config 3 ("processor_grok with nginx combined patterns").
Upstream states the processor and no generator, so the line is stated here
(the configuration lists every item under ``assumed``): ``line_bytes`` bytes
with the newline, the combined log format with the two timing fields a common
``log_format`` extension appends —

    <ip> - <user or -> [dd/Mon/yyyy:HH:MM:SS +0000] "<verb> <path>?<query> HTTP/1.1" <status> <bytes or -> "<referrer>" "<agent>" <request_time> <upstream_time>

— with a 12-digit sequence number in the captured ``request``
(``/api/v1/resource/<12 digits>?...``) in the same columns of every line: the
client address and the user name share a fixed head (a line without a user
carries ``-`` and an IPv6 client address of the width that leaves), and the
query string takes up the width's slack, as in ``apache_templates``.

Template classes (``mix``: counts of the pool by largest remainders, the
name of a class is the position in the ``Match`` list its lines are FOR):

    member1    both timing fields are numbers: every member-1 line also fully
               matches member 2, whose last field takes any token — the list's
               order decides its fields
    member2    the upstream time is ``-`` (no upstream was asked), which
               ``%{NUMBER}`` refuses
    member3    no timing fields: a vhost still on the default combined format
    member4    a common-format line: no referrer, agent or timing fields
    unmatched  no member matches, two kinds in turn: the request line cut
               short (no closing quote, nothing after it); ``(`` where the
               time's opening bracket belongs

Statuses come in ``status_mix``'s shares over the parsable templates; a 304
carries ``-`` for its bytes, so ``bytes`` is absent from its record.  ASCII
only.  Line ``j`` takes template ``mix(seed, j) % pool`` (the arithmetic of
``apache_templates``): a pure function of (seed, j).
"""

from __future__ import annotations

import random

import numpy as np

from benchlib import spec

_apache = spec.load_module("sources", "apache_templates")
SEQ_DIGITS = _apache.SEQ_DIGITS
_ANCHOR = _apache._ANCHOR.decode()
_HEAD_VARIABLE = 28          # client address + user + verb, in bytes
_VERBS = ("GET", "POST", "PUT", "HEAD")
_MONTHS = ("Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep",
           "Oct", "Nov", "Dec")
_HEX = "0123456789abcdef"
_REFERRERS = ("-", "https://www.example.com/", "https://shop.example.com/cart",
              "https://www.example.com/search?q=tpu",
              "https://news.example.org/a/2026/10")
_AGENTS = ("curl/8.5.0", "Go-http-client/2.0", "python-requests/2.31.0",
           "Mozilla/5.0 (X11; Linux x86_64) Gecko/20100101 Firefox/128.0",
           "Mozilla/5.0 (Windows NT 10.0; Win64; x64) Chrome/126.0.0.0",
           "Mozilla/5.0 (iPhone; CPU iPhone OS 17_5 like Mac OS X) Mobile",
           "kube-probe/1.29", "Prometheus/2.53.0")
KINDS = ("member1", "member2", "member3", "member4", "unmatched")


class NginxTemplates(_apache.ApacheTemplates):
    """The pool is this source's own; the stream over it (``template_of``,
    ``block``, ``block_at``, ``line``, ``seqs_in``: the same anchor before
    the sequence digits) is ``apache_templates``'s."""

    def __init__(self, params: dict, seed: int):
        self.seed = int(seed)
        self.line_bytes = int(params["line_bytes"])
        self.pool = int(params["pool"])
        mix = {k: float(params["mix"][k]) for k in KINDS}
        kinds = _apache.apportion(mix, self.pool)
        r = random.Random(self.seed)
        statuses = _apache.apportion(
            params["status_mix"], sum(k != "unmatched" for k in kinds))
        r.shuffle(kinds)
        r.shuffle(statuses)
        rows, offs, self.kinds = [], [], []
        cut = 0
        for k, kind in enumerate(kinds):
            status = statuses.pop() if kind != "unmatched" \
                else r.choice(sorted(params["status_mix"]))
            doc = {"kind": kind, "status": status}
            if kind == "unmatched":
                doc["fault"] = ("cut", "bracket")[cut % 2]
                cut += 1
            line, off = self._template(r, k, doc)
            rows.append(np.frombuffer(line, np.uint8))
            offs.append(off)
            self.kinds.append(doc)
        self.templates = np.stack(rows)                  # [pool, line_bytes]
        if len(set(offs)) != 1:
            raise ValueError("templates disagree on the sequence columns")
        self.seq_offset = offs[0]                        # where the digits go

    def _template(self, r: random.Random, k: int, doc: dict):
        verb = r.choice(_VERBS)
        if r.random() < 0.2:
            # no user: an IPv6 client address takes up the fixed head
            user = "-"
            width = _HEAD_VARIABLE - 1 - len(verb)       # 23 or 24 bytes
            ip = f"2001:db8:{k & 0xffff:04x}:" + "".join(
                r.choice(_HEX) for _ in range(4)) + "::"
            ip += "".join(r.choice(_HEX) for _ in range(width - len(ip)))
        else:
            ip = f"10.{r.randrange(256)}.{(k >> 8) & 255}.{k & 255}"
            user = "u" + "".join(r.choice("0123456789") for _ in range(
                _HEAD_VARIABLE - len(ip) - len(verb) - 1))
        doc["user"] = user
        stamp = (f"{r.randrange(1, 29):02d}/{r.choice(_MONTHS)}/2026:"
                 f"{r.randrange(24):02d}:{r.randrange(60):02d}:"
                 f"{r.randrange(60):02d} +0000")
        head = f'{ip} - {user} [{stamp}] "{verb} '
        off = len(head) + len(_ANCHOR)
        status = doc["status"]
        size = "-" if status == "304" else str(r.randrange(100, 1000000))
        doc["bytes"] = size
        request_time = f"{r.randrange(0, 3)}.{r.randrange(1000):03d}"
        upstream = f"{r.randrange(0, 3)}.{r.randrange(1000):03d}"
        tail = f' HTTP/1.1" {status} {size}'
        kind = doc["kind"]
        if kind != "member4":
            tail += f' "{r.choice(_REFERRERS)}" "{r.choice(_AGENTS)}"'
        if kind in ("member1", "unmatched"):
            tail += f" {request_time} {upstream}"
        elif kind == "member2":
            tail += f" {request_time} -"
        if doc.get("fault") == "bracket":               # no opening bracket
            head = head.replace("[", "(", 1)
        elif doc.get("fault") == "cut":                 # the line ends inside
            tail = ""                                   # the request
        room = self.line_bytes - 1 - len(head) - len(_ANCHOR) - SEQ_DIGITS \
            - len(tail)
        line = (head + _ANCHOR + "0" * SEQ_DIGITS + _apache._query(r, room)
                + tail).encode("ascii") + b"\n"
        if len(line) != self.line_bytes:
            raise ValueError("template does not fill the line width")
        return line, off


def make(params: dict, seed: int) -> NginxTemplates:
    return NginxTemplates(params, seed)

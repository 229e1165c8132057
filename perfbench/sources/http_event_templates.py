"""Line source ``http_event_templates``: fixed-width L7 HTTP request events of
a network observer, one space-separated record a line, for a URL classifier.

``BASELINE.json`` config 5 ("eBPF HTTP/network events → TPU regex URL
classification").  Upstream's observer hands its records over from a perf
buffer and states no file form, so the line is stated here (the configuration
lists every item under ``assumed``): ``line_bytes`` bytes with the newline, the
ten fields this repo's observer emits for an HTTP request, in its order, behind
a request id —

    <req_id> <pid> <comm> <local_addr> <remote_addr> <direction> <method> <path> <host> <http_version>

— ``req_id`` a 12-digit sequence number in columns 0–11 of every line.  The
path's query string takes up the width's slack (the path is at least 24 bytes)
wherever the route admits a query; a ``health`` probe's path admits none, so
there the slack sits in ``host`` (a pod's cluster DNS name).

Template classes (``mix``: shares of the pool by largest remainders).  A class
is named after the rule its paths are FOR, the first of the deployment's list
that fully matches them — ``user_orders``, ``user``, ``order`` and ``search``
paths also fully match ``api_other``, which comes after them:

    health       /healthz /readyz /livez /metrics, nothing after
    user_orders  /api/v<n>/users/<id>/orders[/<id>]?<query>
    user         /api/v<n>/users/<id>?<query>
    order        /api/v<n>/orders/<id>?<query>
    search       /api/v<n>/search?<query>
    auth         /login /logout /oauth/token ?<query>
    api_other    /api/v<n>/<resource>[/<rest>]?<query>, the resource not one
                 of the three above
    static       /static/<dirs>/<file>.<js|css|png|svg|woff2>?<query>
    other        no rule matches: /, /favicon.ico, scanner probes
                 (/wp-login.php, /.env), and near misses (/healthz with a
                 query, a source map under /static, an unversioned or
                 capitalised API path)
    reject       a field missing (no ``comm``: the process was gone before it
                 was looked up; no ``http_version``), in turn: nine tokens, so
                 the ten-capture parse regex rejects the line

ASCII only.  Line ``j`` takes template ``mix(seed, j) % pool`` (the arithmetic
of ``apache_templates``): a pure function of (seed, j).
"""

from __future__ import annotations

import random
import re

import numpy as np
from numpy.lib.stride_tricks import sliding_window_view

from benchlib import spec

_apache = spec.load_module("sources", "apache_templates")
SEQ_DIGITS = _apache.SEQ_DIGITS
#: a sink record names its line by the digits that open it: the parsed
#: record's first member, or the whole line kept under rawLog
_SEQ_RX = re.compile(rb'"(?:req_id|rawLog)":\s*"(\d{%d})[ "]' % SEQ_DIGITS)
#: where those digits sit in a record as the agent's serializer writes it,
#: `{"__time__": <10 digits>, "req_id": "<12 digits>", ...` (and the same
#: with "rawLog", a key as long): the tailer holds 8,192 records a MiB of
#: input to their sequence in this cell, four times any other's, and a
#: findall over them costs it three times this gather
_SEQ_AT = len(b'{"__time__": 1700000000, "req_id": ')
_POW10 = 10 ** np.arange(SEQ_DIGITS - 1, -1, -1, dtype=np.int64)
KINDS = ("health", "user_orders", "user", "order", "search", "auth",
         "api_other", "static", "other", "reject")
MIN_PATH = 24
_COMMS = ("nginx", "envoy", "java", "node", "python3", "haproxy", "traefik")
_PORTS = (80, 443, 8080, 8443)
_HOSTS = ("api.example.com", "shop.example.com", "gw.example.net",
          "m.example.com", "edge.example.io")
_VERSIONS = ("1.1", "1.1", "1.1", "2", "1.0")
_METHODS = {
    "health": ("GET",), "user_orders": ("GET", "POST"),
    "user": ("GET", "PUT", "DELETE"), "order": ("GET", "PUT", "DELETE"),
    "search": ("GET",), "auth": ("POST", "GET"),
    "api_other": ("GET", "POST", "PUT"), "static": ("GET", "HEAD"),
    "other": ("GET", "POST", "HEAD"),
}
_HEALTH = ("/healthz", "/readyz", "/livez", "/metrics")
_AUTH = ("/login", "/logout", "/oauth/token")
_RESOURCES = ("products", "cart", "inventory", "payments", "reviews",
              "shipping_rates", "coupons", "sessions")
_STATIC_DIRS = ("js", "css", "img", "fonts", "a/v2")
_STATIC_EXT = ("js", "css", "png", "svg", "woff2")
_OTHER = ("/", "/favicon.ico", "/wp-login.php", "/.env", "/robots.txt",
          "/index.html", "/admin/config.php", "/healthz",
          "/static/app.js.map", "/api/users/12", "/api/v1/Users/12",
          "/cgi-bin/luci")
_ALNUM = "abcdefghijklmnopqrstuvwxyz0123456789"


def _digits(r: random.Random, lo: int, hi: int) -> str:
    return str(r.randrange(10 ** (lo - 1), 10 ** hi))


def _base(r: random.Random, kind: str) -> str:
    """The path of a ``kind`` line before its query string."""
    v = f"/api/v{r.randrange(1, 4)}"
    if kind == "health":
        return r.choice(_HEALTH)
    if kind == "user_orders":
        tail = "/orders" + ("/" + _digits(r, 1, 2) if r.random() < 0.5 else "")
        return f"{v}/users/{_digits(r, 1, 3)}{tail}"
    if kind == "user":
        return f"{v}/users/{_digits(r, 1, 6)}"
    if kind == "order":
        return f"{v}/orders/{_digits(r, 1, 6)}"
    if kind == "search":
        return f"{v}/search"
    if kind == "auth":
        return r.choice(_AUTH)
    if kind == "api_other":
        base = f"{v}/{r.choice(_RESOURCES)}"
        if r.random() < 0.5 and len(base) < 16:
            base += "/" + "".join(r.choice(_ALNUM)
                                  for _ in range(r.randrange(1, 20 - len(base))))
        return base
    if kind == "static":
        ext = r.choice(_STATIC_EXT)
        name = "".join(r.choice(_ALNUM) for _ in range(r.randrange(2, 5)))
        return f"/static/{r.choice(_STATIC_DIRS)}/{name}.{ext}"
    return r.choice(_OTHER)


class HttpEventTemplates(_apache.ApacheTemplates):
    """The pool is this source's own; the stream over it (``template_of``,
    ``block``, ``block_at``, ``line``) is ``apache_templates``'s, with the
    sequence digits at the head of the line."""

    seq_offset = 0

    def __init__(self, params: dict, seed: int):
        self.seed = int(seed)
        self.line_bytes = int(params["line_bytes"])
        self.pool = int(params["pool"])
        mix = {k: float(params["mix"][k]) for k in KINDS}
        kinds = _apache.apportion(mix, self.pool)
        r = random.Random(self.seed)
        r.shuffle(kinds)
        paths = [k for k in KINDS if k != "reject"]
        weights = [mix[k] for k in paths]
        rows, self.kinds = [], []
        n_reject = 0
        for kind in kinds:
            doc = {"kind": kind}
            if kind == "reject":
                doc["missing"] = ("comm", "http_version")[n_reject % 2]
                doc["route"] = r.choices(paths, weights)[0]
                n_reject += 1
            rows.append(np.frombuffer(self._template(r, doc), np.uint8))
            self.kinds.append(doc)
        self.templates = np.stack(rows)                  # [pool, line_bytes]

    def _template(self, r: random.Random, doc: dict) -> bytes:
        route = doc.get("route", doc["kind"])
        fields = {
            "pid": str(r.randrange(100, 100000)),
            "comm": r.choice(_COMMS),
            "local_addr": f"10.0.{r.randrange(4)}.{r.randrange(1, 21)}:"
                          f"{r.choice(_PORTS)}",
            "remote_addr": f"10.{r.randrange(1, 100)}.{r.randrange(256)}."
                           f"{r.randrange(1, 255)}:{r.randrange(32768, 61000)}",
            "direction": "ingress" if r.random() < 0.8 else "egress",
            "method": r.choice(_METHODS[route]),
            "path": "",
            "host": r.choice(_HOSTS),
            "http_version": r.choice(_VERSIONS),
        }
        fields.pop(doc.get("missing"), None)
        # bytes the line has left once every other field, the separating
        # spaces, the sequence digits and the newline are written
        room = self.line_bytes - 1 - SEQ_DIGITS - len(fields) \
            - sum(len(v) for v in fields.values())
        base = _base(r, route)
        while route != "health" and len(base) > room - 4:
            base = _base(r, route)          # leave a query its four bytes
        if route == "health":
            # no query after a probe's path: the pod's DNS name is as long
            # as the line needs
            fields["path"] = base
            stem, dom = r.choice(("gw", "api", "web")) + "-", \
                ".prod.svc.cluster.local"
            fill = room - len(base) + len(fields["host"]) - len(stem) - len(dom)
            fields["host"] = stem + "".join(r.choice(_ALNUM)
                                            for _ in range(fill)) + dom
        else:
            fields["path"] = base + _apache._query(r, room - len(base))
            if len(fields["path"]) < MIN_PATH:
                raise ValueError("the line width leaves the path under "
                                 f"{MIN_PATH} bytes")
        doc["path"] = fields["path"]
        line = ("0" * SEQ_DIGITS + " " + " ".join(fields.values())
                ).encode("ascii") + b"\n"
        if len(line) != self.line_bytes:
            raise ValueError("template does not fill the line width")
        return line

    @staticmethod
    def seqs_in(records: bytes) -> np.ndarray:
        """The sequence number of every sink record in ``records`` (whole
        lines of the sink), in order."""
        seqs = _seqs_by_position(records)
        if seqs is not None:
            return seqs
        found = _SEQ_RX.findall(records)
        if not found:
            return np.empty(0, np.int64)
        return np.array(found, dtype=f"S{SEQ_DIGITS}").astype(np.int64)


def _seqs_by_position(records: bytes):
    """The same by position, for records laid out as the serializer lays
    them out: the digits between a quote and a quote or a space, `_SEQ_AT`
    bytes into every line.  None where any line is laid out otherwise (the
    pattern then decides)."""
    a = np.frombuffer(records, np.uint8)
    ends = np.flatnonzero(a == 10)
    if not ends.size or ends[-1] != a.size - 1:
        return None
    starts = np.empty(ends.size, np.int64)
    starts[0] = 0
    starts[1:] = ends[:-1] + 1
    if (ends - starts).min() <= _SEQ_AT + SEQ_DIGITS + 1:
        return None
    at = sliding_window_view(a, SEQ_DIGITS + 2)[starts + _SEQ_AT]
    digits = at[:, 1:-1]
    last = at[:, -1]
    if not ((at[:, 0] == 34).all() and ((last == 34) | (last == 32)).all()
            and (digits >= 48).all() and (digits <= 57).all()):
        return None
    return (digits.astype(np.int64) - 48) @ _POW10


def make(params: dict, seed: int) -> HttpEventTemplates:
    return HttpEventTemplates(params, seed)

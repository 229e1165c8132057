"""Line source ``apache_secret_templates``: Apache access-log lines of an API
gateway whose signed requests carry credentials in their URLs.

The deployment is upstream's credential masking (``processor_desensitize_native``
behind the Apache parse); the request line follows upstream's documented
sample, ``POST /PutData?Category=...&AccessKeyId=<yourAccessKeyId>&Date=...&
Topic=raw&Signature=<yourSignature> HTTP/1.1``.  The layout is
``apache_templates``': fixed-width lines (``line_bytes`` with the newline), a
fixed head so that the 12-digit sequence number sits in the same columns of
every line, inside the captured ``url`` before the ``?``
(``/api/v1/resource/<12 digits>?<query>``), and the statuses in the shares of a
published corpus (``status_mix``).  The query string takes up the width's
slack: the credentials among plain ``key=value`` parameters, the last plain
value as long as the line needs.

Template classes (``mix``: shares of the pool, largest remainders):

    both       AccessKeyId=LTAI<20 alphanumerics> and Signature=<28 bytes of
               URL-encoded base64>, as in upstream's sample: 2 matches
    signature  Signature only (a pre-signed link): 1
    sts        both and SecurityToken=<120 bytes> (temporary credentials): 3
    none       no credential: 0
    edge       a quarter each: an empty Signature (``Signature=&``, not
               masked) beside an AccessKeyId; both credentials as the last
               parameters, so a match runs to the field's end; a look-alike
               key ``XAccessKeyId=`` (masked by the pattern's own semantics)
               beside a Signature; an encoded ``%26Signature%3D`` inside
               another value (not masked) beside an AccessKeyId
    reject     lines the parse pattern rejects (``apache_templates``' three
               kinds); each carries both credentials and ships whole

Line ``j`` takes template ``mix(seed, j) % pool`` (``apache_templates``'
arithmetic): a pure function of (seed, j).

``needs_of_program`` states what the checkout under test has to hold for the
deployment to be this one (``json_templates.hold_program_to``): ``make``
refuses a checkout that lacks it with a ``SpecError`` before anything is
built or started.
"""

from __future__ import annotations

import random

import numpy as np

from benchlib import spec

_apache = spec.load_module("sources", "apache_templates")
_json = spec.load_module("sources", "json_templates")
SEQ_DIGITS = _apache.SEQ_DIGITS
KINDS = ("both", "signature", "sts", "none", "edge", "reject")
EDGES = ("empty_signature", "last_parameter", "look_alike", "encoded")
_ANCHOR = "/api/v1/resource/"
_METHODS = ("GET", "POST", "PUT", "DELETE")
_HEAD_VARIABLE = 28          # ip + user + method, in bytes
_ALNUM = "ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789"
_B64 = _ALNUM + "+/"
_FILL_KEYS = ("Category", "Date", "Topic", "Project", "LogStore", "id", "q",
              "ref", "page", "lang", "ts", "trace")
_FILL_CHARS = "abcdefghijklmnopqrstuvwxyz0123456789"


def _url_b64(r: random.Random, size: int) -> str:
    """``size`` bytes of URL-encoded base64, ending in its ``=`` padding."""
    out = ""
    while len(out) < size - 3:
        c = r.choice(_B64)
        code = {"+": "%2B", "/": "%2F"}.get(c, c)
        out += code if len(out) + len(code) <= size - 3 else r.choice(_ALNUM)
    return out + "%3D"


def _access_key_id(r: random.Random) -> str:
    return "LTAI" + "".join(r.choice(_ALNUM) for _ in range(20))


def _width(params) -> int:
    """Bytes of ``params`` joined by ``&``."""
    return sum(len(p) for p in params) + max(len(params) - 1, 0)


def _fill(r: random.Random) -> str:
    return r.choice(_FILL_KEYS) + "=" + "".join(
        r.choice(_FILL_CHARS) for _ in range(r.randrange(4, 25)))


class ApacheSecretTemplates:
    def __init__(self, params: dict, seed: int):
        self.seed = int(seed)
        self.line_bytes = int(params["line_bytes"])
        self.pool = int(params["pool"])
        r = random.Random(self.seed)
        kinds = _apache.apportion(params["mix"], self.pool)
        statuses = _apache.apportion(params["status_mix"], self.pool)
        r.shuffle(kinds)
        r.shuffle(statuses)
        n_edge = kinds.count("edge")
        edges = [EDGES[i * len(EDGES) // n_edge] for i in range(n_edge)]
        r.shuffle(edges)
        rows, offs = [], []
        self.kinds = []
        for k, (kind, status) in enumerate(zip(kinds, statuses)):
            edge = edges.pop() if kind == "edge" else None
            line, off = self._template(r, k, kind, edge, status)
            rows.append(np.frombuffer(line, np.uint8))
            offs.append(off)
            self.kinds.append({"kind": kind, "edge": edge})
        self.templates = np.stack(rows)                  # [pool, line_bytes]
        if len(set(offs)) != 1:
            raise ValueError("templates disagree on the sequence columns")
        self.seq_offset = offs[0]

    def _credentials(self, r: random.Random, kind: str, edge):
        """(parameters among the plain ones, parameters that end the query)."""
        key = "AccessKeyId=" + _access_key_id(r)
        sig = "Signature=" + _url_b64(r, 28)
        if kind in ("both", "reject"):
            return [key, sig], []
        if kind == "signature":
            return [sig], []
        if kind == "sts":
            return [key, sig, "SecurityToken=" + _url_b64(r, 120)], []
        if kind == "none":
            return [], []
        if edge == "empty_signature":
            return [key, "Signature="], []
        if edge == "last_parameter":
            return [], [key, sig]
        if edge == "look_alike":
            return ["X" + key, sig], []
        # encoded: a callback URL whose own query names a Signature
        return [key, "ref=https%3A%2F%2Fcb.example%2Fdone%3Fa%3D1%26Signature"
                     "%3D" + "".join(r.choice(_ALNUM) for _ in range(12))], []

    def _template(self, r: random.Random, k: int, kind: str, edge, status):
        ip = f"10.{r.randrange(256)}.{(k >> 8) & 255}.{k & 255}"
        method = r.choice(_METHODS)
        user = "u" + "".join(r.choice("0123456789") for _ in range(
            _HEAD_VARIABLE - len(ip) - len(method) - 1))
        head = (f"{ip} - {user} "
                f"[10/Oct/2000:13:{r.randrange(60):02d}:{r.randrange(60):02d} "
                f'-0700] "{method} ')
        off = len(head) + len(_ANCHOR)
        tail = f' HTTP/1.1" {status} {r.randrange(100, 1000000)}'
        if kind == "reject":
            how = r.randrange(3)
            if how == 0:                        # no opening bracket
                head = head.replace("[", "(", 1)
            elif how == 1:                      # two-digit status
                tail = f' HTTP/1.1" {status[:2]} {r.randrange(100, 1000000)}'
            else:                               # a non-digit ends the size
                tail = tail[:-1] + "x"
        inner, last = self._credentials(r, kind, edge)
        room = self.line_bytes - 1 - len(head) - len(_ANCHOR) - SEQ_DIGITS \
            - len(tail) - 1
        fills = []
        while room - _width(inner + fills + last) - 1 >= 40:
            fills.append(_fill(r))          # at most 33 bytes with its '&'
        if not fills:
            raise ValueError("the line width leaves no room for the query")
        # the last plain value takes up what is left, exactly
        fills[-1] += "".join(r.choice(_FILL_CHARS) for _ in range(
            room - _width(inner + fills + last)))
        params = list(inner)
        for f in fills:
            params.insert(r.randrange(len(params) + 1), f)
        query = "&".join(params + last)
        line = (head + _ANCHOR + "0" * SEQ_DIGITS + "?" + query
                + tail).encode("ascii") + b"\n"
        if len(line) != self.line_bytes:
            raise ValueError("template does not fill the line width")
        return line, off

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

    seqs_in = staticmethod(_apache.ApacheTemplates.seqs_in)


def make(params: dict, seed: int) -> ApacheSecretTemplates:
    _json.hold_program_to(params["needs_of_program"])
    return ApacheSecretTemplates(params, seed)

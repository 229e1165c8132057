"""json_fields: the resident JSON stage — a [B, L] slot of rows in, the field
spans of every row's top-level object out.

This is what the structural-index twin (struct_index.py) never had: the
step from byte masks to the ``(offset, length)`` of a value.  The stage
publishes what an ``extract`` stage publishes — ``ok[B]``, ``off[B, C]``,
``len[B, C]`` — so every later member of a fused run (a filter's
``span_match`` condition, the keep-mask compaction) binds it unchanged, plus
a per-row ``status``, the member count and a signature of the key names.

Layer equations, all on ``[B, L]`` (b = byte, p = position, n = row length):

  masks       (struct_index._index_core)  q = unescaped quote (a quote whose
              preceding backslash run is even); in_string = prefix-XOR of q
              (opening quote inside, closing quote outside); s = one of
              ``{ } [ ] : ,`` outside strings
  depth       d = cumsum(s & open) − cumsum(s & close), inclusive; a token
              sits in the container of level lvl = d − open + close (an
              opening bracket in its parent, a closing one in the container
              it closes); top-level colons and commas are those with lvl = 1;
              the row is closed where d returns to 0 and no token follows
  members     r = cumsum(top-level comma) is the member rank, cc =
              cumsum(top-level colon); the key of member k is the string in
              the region cc = r = k, its value runs from the first token
              after the colon of rank k to the last token before the comma of
              rank k (or the closing brace), so it is trimmed of whitespace
              by construction; a string value's span is inside its quotes
  tokens      every byte outside strings that is not whitespace, and every
              closing quote (one token per string); P(p) = class of the last
              token before p, PP(p) = class of the last non-string token
              before p (two max-scans of ``16·p + class``); ctx(p) = kind
              (object / array) of the container a token sits in, from one
              max-scan per nesting level 2 … DMAX of ``2·p + is_brace`` over
              the brackets that open that level (level 1 is the row's object)
  grammar     a row is proven when every token passes its rule on (P, PP,
              ctx) — the rules are JSON's own, written out in ``_grammar`` —
              every scalar run is ``true``/``false``/``null`` or a JSON
              number (local rules on neighbouring bytes plus one max-scan that
              marks run start / ``.`` / exponent, so that a second ``.`` or
              exponent in a run is seen), no string holds a control byte, d
              never passes DMAX and ends at 0, and the row has at most KMAX
              members
  positional  members 0 … KMAX−1: value spans, by 2·KMAX masked sums over L
  signature   two 32-bit sums over the key bytes (opening quotes included,
              so that key boundaries count) of ``(b + 1) · mix(j)``, j the
              running count of key bytes: rows with the same key names in
              the same order have the same signature whatever their values,
              and the host decodes the names once per signature
  named       a bound key κ (the names later members of the run bind) is
              found by comparing its bytes at every top-level key's opening
              quote; exactly one match publishes that member's span as
              capture KMAX + i, none publishes it absent, two make the row
              unprovable (which one wins is the host's rule to apply)

``status`` per row: 0 ok; 1 a string holds a backslash (its decoded bytes
differ from its span — the host's emitter decodes it); 2 a shape the
equations cannot prove (unbalanced, trailing bytes, an invalid scalar, a
control byte in a string, deeper than DMAX, more than KMAX members, a bound
key twice, a row longer than L); 3 not an object.  Nothing is guessed: a row
that is not ok publishes no span, and the caller hands it whole to the
host's emitter.

Pure ``jnp``: cumulative sums and max-scans along L and elementwise passes;
one jitted program per (B, L) geometry under the name
``jit_loong_json_fields`` when dispatched by itself (the fused program that
carries it is ``jit_loong_fused_program``).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

from .struct_index import MODE_JSON, _index_core

#: positional members a row may have and still be proven on the device
KMAX = 16
#: deepest nesting the context scans cover (the row's object is level 1)
DMAX = 4

STATUS_OK = 0
STATUS_ESCAPE = 1
STATUS_SHAPE = 2
STATUS_NOT_OBJECT = 3
STATUS_NAMES = ("ok", "escape", "shape", "not_object")

# token classes (4 bits beside the position in the scans)
_LBRACE, _RBRACE, _LBRACK, _RBRACK, _COLON, _COMMA, _STR, _SC = range(1, 9)


class JsonFieldsPlan:
    """The stage's payload: how many positional members, and the keys later
    members of the run bound (grown by the planner while the run is
    planned, fixed from the first dispatch on).  Names are the planner's
    strings: a key's bytes decoded as latin-1."""

    def __init__(self, kmax: int = KMAX):
        self.kmax = kmax
        self.bound: List[str] = []

    @property
    def num_caps(self) -> int:
        return self.kmax + len(self.bound)

    def bind(self, name: str) -> int:
        """Capture index of the named key, minted on first use."""
        if name not in self.bound:
            self.bound.append(name)
        return self.kmax + self.bound.index(name)


def build_json_fields_fn(kmax: int, bound: Sequence[str]):
    """jit-able f(rows u8 [B,L], lengths i32 [B]) -> (ok bool [B],
    off i32 [B,C], len i32 [B,C], status i32 [B], members i32 [B],
    signature i32 [B,2]) with C = kmax + len(bound); spans are
    row-relative, absent captures have length −1."""
    import jax.numpy as jnp
    from jax import lax

    bound_bytes = [k.encode("latin-1") for k in bound]

    def scan_max(a):
        return lax.cummax(a, axis=1)

    def fields(rows, lengths):
        B, L = rows.shape
        lengths = lengths.astype(jnp.int32)
        i32 = jnp.int32

        def right(a, k=1, fill=0):
            """a shifted towards higher positions: out[p] = a[p − k]."""
            pad = jnp.full((B, k), fill, dtype=a.dtype)
            return jnp.concatenate([pad, a[:, :L - k]], axis=1)

        def left(a, k=1, fill=0):
            """out[p] = a[p + k]."""
            if k >= L:
                return jnp.full((B, L), fill, dtype=a.dtype)
            pad = jnp.full((B, k), fill, dtype=a.dtype)
            return jnp.concatenate([a[:, k:], pad], axis=1)

        pos = jnp.arange(L, dtype=i32)[None, :] + jnp.zeros((B, 1), i32)
        valid = pos < lengths[:, None]
        in_string, structural, _escaped, q_real = _index_core(
            rows, lengths, MODE_JSON, 0, jnp, scan_max)
        q_open = q_real & in_string
        q_close = q_real & ~in_string
        outside = valid & ~in_string & ~q_close
        ws = outside & ((rows == 0x20) | (rows == 0x09) | (rows == 0x0A)
                        | (rows == 0x0D))
        lbrace = structural & (rows == 0x7B)
        rbrace = structural & (rows == 0x7D)
        lbrack = structural & (rows == 0x5B)
        rbrack = structural & (rows == 0x5D)
        colon = structural & (rows == 0x3A)
        comma = structural & (rows == 0x2C)
        opens = lbrace | lbrack
        closes = rbrace | rbrack
        sc = outside & ~structural & ~ws

        d = jnp.cumsum(opens.astype(i32) - closes.astype(i32), axis=1)
        lvl = d - opens.astype(i32) + closes.astype(i32)

        tok = (lbrace * _LBRACE + rbrace * _RBRACE + lbrack * _LBRACK
               + rbrack * _RBRACK + colon * _COLON + comma * _COMMA
               + q_close * _STR + sc * _SC).astype(i32)
        is_tok = tok > 0
        last = right(scan_max(jnp.where(is_tok, pos * 16 + tok, -1)),
                     fill=-1)
        P = jnp.where(last >= 0, last & 15, 0)
        prev_pos = last >> 4
        last_ns = right(scan_max(jnp.where(is_tok & ~q_close,
                                           pos * 16 + tok, -1)), fill=-1)
        PP = jnp.where(last_ns >= 0, last_ns & 15, 0)

        # ctx: is the container this token sits in an object?
        ctx_obj = lvl == 1
        for level in range(2, DMAX + 1):
            t = scan_max(jnp.where(opens & (d == level),
                                   pos * 2 + lbrace, -1))
            ctx_obj = ctx_obj | ((lvl == level) & ((t & 1) == 1) & (t >= 0))

        bad = _grammar(tok, P, PP, ctx_obj, lvl, sc, right(sc))
        bad = bad | _scalars(jnp, scan_max, rows, sc, pos, left, right)
        bad = bad | (in_string & ~q_real & (rows < 0x20))
        bad = bad | (valid & ((d > DMAX) | (d < 0)))
        row_bad = bad.any(axis=1)
        # closed: depth back at 0 and no string left open at the row's end
        row_bad = row_bad | (d[:, L - 1] != 0) \
            | ((jnp.sum(q_real, axis=1) % 2) == 1) | (lengths > L)

        first = is_tok & (P == 0)
        not_object = ~(first & lbrace).any(axis=1)
        has_bs = ((rows == 0x5C) & in_string).any(axis=1)

        # -- members ---------------------------------------------------------
        top = lvl == 1
        topcolon = colon & top
        topcomma = comma & top
        cc = jnp.cumsum(topcolon.astype(i32), axis=1)
        r = jnp.cumsum(topcomma.astype(i32), axis=1)
        n_colon = cc[:, L - 1]
        members = jnp.where(n_colon > 0, r[:, L - 1] + 1, 0)
        row_bad = row_bad | (members > kmax)

        sc_start = sc & ~right(sc)
        vstart = (q_open | ((lbrace | lbrack) & top) | sc_start) \
            & (P == _COLON) & (jnp.where(q_open | sc_start, d, lvl) == 1)
        term = topcomma | (rbrace & top)
        term_rank = r - topcomma.astype(i32)
        start_code = jnp.where(vstart, pos * 2 + q_open, 0)
        end_code = jnp.where(term, prev_pos, 0)
        offs, lens = [], []
        for k in range(kmax):
            code = jnp.sum(jnp.where(r == k, start_code, 0), axis=1)
            end = jnp.sum(jnp.where(term_rank == k, end_code, 0), axis=1)
            is_str = code & 1
            start = (code >> 1) + is_str
            offs.append(start)
            lens.append(jnp.where(k < members,
                                  end + 1 - is_str - start, -1))

        # -- the signature of the key names ----------------------------------
        key_byte = in_string & (d == 1) & (cc == r)
        j = jnp.cumsum(key_byte.astype(i32), axis=1).astype(jnp.uint32)
        b1 = rows.astype(jnp.uint32) + jnp.uint32(1)
        sig = []
        for mul, mul2 in ((0x9E3779B1, 0x85EBCA77), (0xC2B2AE3D, 0x27D4EB2F)):
            m = j * jnp.uint32(mul)
            m = (m ^ (m >> jnp.uint32(15))) * jnp.uint32(mul2)
            m = (m ^ (m >> jnp.uint32(13))) | jnp.uint32(1)
            sig.append(lax.bitcast_convert_type(
                jnp.sum(jnp.where(key_byte, b1 * m, jnp.uint32(0)), axis=1),
                i32))

        # -- named captures --------------------------------------------------
        key_open = q_open & (d == 1) & (cc == r)
        pos_off = jnp.stack(offs, axis=1)
        pos_len = jnp.stack(lens, axis=1)
        slot = jnp.arange(kmax, dtype=i32)[None, :]
        for kb in bound_bytes:
            hit = key_open & left(q_close, len(kb) + 1, False)
            for i, byte in enumerate(kb):
                hit = hit & (left(rows, i + 1) == byte)
            n_hit = jnp.sum(hit, axis=1)
            at = jnp.sum(jnp.where(hit, r, 0), axis=1)
            row_bad = row_bad | (n_hit > 1)
            pick = (slot == at[:, None]) & (n_hit == 1)[:, None]
            offs.append(jnp.sum(jnp.where(pick, pos_off, 0), axis=1))
            lens.append(jnp.where(
                n_hit == 1, jnp.sum(jnp.where(pick, pos_len, 0), axis=1), -1))

        status = jnp.where(
            not_object, STATUS_NOT_OBJECT,
            jnp.where(row_bad, STATUS_SHAPE,
                      jnp.where(has_bs, STATUS_ESCAPE, STATUS_OK))).astype(i32)
        ok = status == STATUS_OK
        off = jnp.where(ok[:, None], jnp.stack(offs, axis=1), 0).astype(i32)
        ln = jnp.where(ok[:, None], jnp.stack(lens, axis=1), -1).astype(i32)
        return (ok, off, ln, status, members.astype(i32),
                jnp.stack(sig, axis=1))

    return fields


def _grammar(tok, P, PP, ctx_obj, lvl, sc, sc_before):
    """JSON's token rules; True where a token breaks one.  ``P`` is the
    class of the token before, ``PP`` of the last token before that is not
    a string (so, behind a string, the token the string followed), and
    ``ctx_obj`` whether the token sits in an object (an opening bracket in
    its parent, a closing one in the container it closes)."""
    def is_(c):
        return tok == c

    def p_in(*cs):
        out = P == cs[0]
        for c in cs[1:]:
            out = out | (P == c)
        return out

    in_arr = ~ctx_obj
    value_end = p_in(_SC, _RBRACE, _RBRACK)
    # a value may start after a colon, after '[', or after a comma in an array
    value_ok = p_in(_COLON, _LBRACK) | ((P == _COMMA) & in_arr)
    first = P == 0
    bad = first & (tok > 0) & ~is_(_LBRACE)
    bad = bad | ((tok > 0) & ~first & (lvl < 1))       # bytes behind the object
    bad = bad | ((is_(_LBRACE) | is_(_LBRACK)) & ~first & ~value_ok)
    bad = bad | (sc & ~sc_before & ~value_ok)          # a scalar's first byte
    bad = bad | (is_(_STR) & ~p_in(_LBRACE, _COMMA, _COLON, _LBRACK))
    str_is_value = (PP == _COLON) | (PP == _LBRACK) \
        | ((PP == _COMMA) & in_arr)
    bad = bad | (is_(_COLON) & ~((P == _STR) & ctx_obj
                                 & ((PP == _LBRACE) | (PP == _COMMA))))
    bad = bad | (is_(_COMMA) & ~(value_end | ((P == _STR) & str_is_value)))
    bad = bad | (is_(_RBRACE) & ~(ctx_obj & (
        (P == _LBRACE) | value_end | ((P == _STR) & (PP == _COLON)))))
    bad = bad | (is_(_RBRACK) & ~(in_arr & (
        (P == _LBRACK) | value_end
        | ((P == _STR) & ((PP == _LBRACK) | (PP == _COMMA))))))
    return bad


def _scalars(xp, scan_max, rows, sc, pos, left, right):
    """True where a byte of a scalar run (outside strings, not structural,
    not whitespace) cannot belong to ``true``, ``false``, ``null`` or a
    JSON number."""
    c = xp.where(sc, rows, 0).astype(xp.int32)
    nxt = left(c)
    prv = right(c)
    run_start = sc & (prv == 0)
    run_end = sc & (nxt == 0)

    def word(text: bytes):
        hit = run_start & (left(c, len(text)) == 0)
        for i, byte in enumerate(text):
            hit = hit & (left(c, i) == byte if i else c == byte)
        cover = hit
        for i in range(1, len(text)):
            cover = cover | right(hit, i, False)
        return cover

    covered = word(b"true") | word(b"false") | word(b"null")
    digit = (c >= 0x30) & (c <= 0x39)
    nxt_digit = (nxt >= 0x30) & (nxt <= 0x39)
    prv_digit = (prv >= 0x30) & (prv <= 0x39)
    minus, plus, dot = c == 0x2D, c == 0x2B, c == 0x2E
    exp = (c == 0x65) | (c == 0x45)
    prv_exp = (prv == 0x65) | (prv == 0x45)
    # the marker before this byte in its run: 1 run start, 2 '.', 3 exponent
    mark = xp.where(run_start, 1, xp.where(dot, 2, xp.where(exp, 3, 0)))
    before = right(scan_max(xp.where(sc & (mark > 0), pos * 4 + mark, -1)),
                   fill=-1) & 3
    num = sc & ~covered
    bad = num & ~(digit | minus | plus | dot | exp)
    bad = bad | (num & minus & ~((run_start | prv_exp) & nxt_digit))
    bad = bad | (num & plus & ~(prv_exp & nxt_digit))
    bad = bad | (num & dot & ~(prv_digit & nxt_digit & (before == 1)))
    bad = bad | (num & exp & ~(prv_digit & (before != 3) & (
        nxt_digit | (nxt == 0x2D) | (nxt == 0x2B))))
    leading = run_start | ((prv == 0x2D) & right(run_start, 1, False))
    bad = bad | (num & leading & (c == 0x30) & nxt_digit)
    bad = bad | (num & run_start & ~(digit | minus))
    bad = bad | (num & run_end & ~digit)
    return bad


class JsonFieldsKernel:
    """The stage dispatched by itself (``jit_loong_json_fields``): the
    per-stage twin a fused chunk demotes to, and what the tests drive."""

    def __init__(self, plan: JsonFieldsPlan):
        self.plan = plan
        self._fn = None
        self._bound: Tuple[str, ...] = ()
        self.dispatch_count = 0

    def __call__(self, rows, lengths):
        bound = tuple(self.plan.bound)
        if self._fn is None or bound != self._bound:
            from ..compile_watch import watched_jit
            self._fn = watched_jit(
                build_json_fields_fn(self.plan.kmax, bound), "json_fields")
            self._bound = bound
        self.dispatch_count += 1
        return self._fn(rows, lengths)

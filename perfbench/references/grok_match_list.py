"""Plain reference ``grok_match_list``: an ordered grok ``Match`` list, one line
at a time with Python's ``re``.

It imports nothing of the program and reads nothing the program made.  Its
parameters are the ``reference`` object of a configuration's ``config.json``:

    match   the grok expressions, in the order the deployment tries them

For a line, ``re.fullmatch`` of each expanded member in order; the first that
matches decides the record: its named groups in the pattern's order, a group
that took no part in the match absent.  A line no member matches is kept
whole under ``rawLog`` (the processor's KeepingSourceWhenParseFail default).
``__time__`` is the read clock's (epoch None): the deployment has no
timestamp processor.

The pattern library below is this file's own copy of the entries the access-
log expressions use (the public grok vocabulary; the request and the two
quoted fields in their negated-class forms, which the deployment's library
documents: same language on well-formed lines), expanded by ``expand``.
"""

from __future__ import annotations

import re

LIBRARY = {
    "INT": r"[+-]?\d+",
    "POSINT": r"\d+",
    "BASE10NUM": r"[+-]?(?:\d+(?:\.\d+)?|\.\d+)",
    "NUMBER": r"%{BASE10NUM}",
    "WORD": r"\w+",
    "NOTSPACE": r"\S+",
    "NOTSPACEQ": r'[^ "]+',
    "MONTH3": r"(?:Jan|Feb|Mar|Apr|May|Jun|Jul|Aug|Sep|Oct|Nov|Dec)",
    "MONTHDAY2": r"(?:3[01]|[12][0-9]|0[1-9])",
    "YEAR": r"(?:\d\d){1,2}",
    "HOUR2": r"(?:2[0-3]|[01][0-9])",
    "MINUTE": r"(?:[0-5][0-9])",
    "SECOND": r"(?:[0-5][0-9]|60)(?:[:.,][0-9]+)?",
    "TIME": r"%{HOUR2}:%{MINUTE}(?::%{SECOND})?",
    "HTTPDATE": r"%{MONTHDAY2}/%{MONTH3}/%{YEAR}:%{TIME} %{INT}",
    "COMMONAPACHELOG": (
        r'%{NOTSPACE:clientip} %{NOTSPACE:ident} %{NOTSPACE:auth} '
        r'\[%{HTTPDATE:timestamp}\] "%{WORD:verb} %{NOTSPACEQ:request}'
        r'(?: HTTP/%{NUMBER:httpversion})?" %{INT:response} '
        r'(?:%{POSINT:bytes}|-)'),
    "COMBINEDAPACHELOG": (
        r'%{COMMONAPACHELOG} "(?P<referrer>[^"]*)" "(?P<agent>[^"]*)"'),
}

_REF = re.compile(r"%\{(\w+)(?::(\w+))?\}")


def expand(expression: str, depth: int = 0) -> str:
    """``%{NAME}`` → the library entry as a group, ``%{NAME:field}`` → as a
    group named ``field``; entries may hold references of their own."""
    if depth > 8:
        raise ValueError("the pattern library refers to itself")

    def one(m):
        body = expand(LIBRARY[m.group(1)], depth + 1)
        return f"(?P<{m.group(2)}>{body})" if m.group(2) else f"(?:{body})"
    return _REF.sub(one, expression)


class GrokMatchList:
    def __init__(self, params: dict):
        self.members = [re.compile(expand(m).encode("latin-1"))
                        for m in params["match"]]

    def member_of(self, line: bytes):
        """Index of the first member that fully matches, or None."""
        for i, rx in enumerate(self.members):
            if rx.fullmatch(line) is not None:
                return i
        return None

    def expected(self, line: bytes):
        """``(record, None)`` the deployment must emit for one input line
        (newline stripped)."""
        for rx in self.members:
            m = rx.fullmatch(line)
            if m is not None:
                return {k: v.decode("latin-1")
                        for k, v in m.groupdict().items()
                        if v is not None}, None
        return {"rawLog": line.decode("latin-1")}, None


def make(params: dict) -> GrokMatchList:
    return GrokMatchList(params)

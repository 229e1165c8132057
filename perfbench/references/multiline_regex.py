"""Plain reference ``multiline_regex``: start-pattern multiline merge → regex
parse, one unit of the stream at a time with Python's ``re``.

It imports nothing of the program and reads nothing the program made.  Its
parameters are the ``reference`` object of a configuration's ``config.json``:

    start_pattern  the reader's Multiline.StartPattern: a physical line opens a
                   record when the WHOLE line matches
    regex          the parse pattern; a record is parsed when the WHOLE record
                   (its final newline stripped, the embedded ones kept) matches
    keys           one per capture

One unit is one record: its first physical line must fully match the start
pattern and no later line may (a unit that breaks either would change a
neighbouring unit's record, which no per-unit reference can express — the
line source makes none, and this raises ``ValueError`` on one rather than
answer).  A record the parse regex rejects is kept whole under ``rawLog`` (the
processor's KeepingSourceWhenParseFail default).  ``__time__`` is the read
clock's (epoch None): the deployment has no timestamp processor.
"""

from __future__ import annotations

import re


class MultilineRegex:
    def __init__(self, params: dict):
        self.start = re.compile(params["start_pattern"].encode("latin-1"))
        self.rx = re.compile(params["regex"].encode("latin-1"))
        self.keys = list(params["keys"])

    def expected(self, unit: bytes):
        """``(record, None)`` the deployment must emit for one unit (final
        newline stripped)."""
        lines = unit.split(b"\n")
        if self.start.fullmatch(lines[0]) is None:
            raise ValueError("the unit's first line does not open a record")
        if any(self.start.fullmatch(ln) is not None for ln in lines[1:]):
            raise ValueError("a later line of the unit opens a record")
        m = self.rx.fullmatch(unit)
        if m is None:
            return {"rawLog": unit.decode("latin-1")}, None
        return {k: g.decode("latin-1")
                for k, g in zip(self.keys, m.groups())}, None


def make(params: dict) -> MultilineRegex:
    return MultilineRegex(params)

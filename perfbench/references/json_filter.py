"""Plain reference ``json_filter``: JSON parse → include-filter, one line at a
time with Python's ``json`` and ``re``.

It imports nothing of the program and reads nothing the program made.  Its
parameters are the ``reference`` object of a configuration's ``config.json``:

    include   {key: pattern}: a parsed record is kept when every named field
              fully matches; a record without the field is dropped, as
              upstream's processor_filter_native does

A line that is one JSON object becomes one record: a top-level string is its
decoded text, any other value its compact JSON text (``separators=(",", ":")``,
non-ASCII kept) — the program ships a value's raw token, and the line source
writes numbers, booleans, ``null``, nested objects and arrays without optional
whitespace, so the two agree (the configuration states that under
``assumed``).  A line that does not parse is kept whole under ``rawLog`` (the
processor's KeepingSourceWhenParseFail default); it has none of the filter's
fields, so a filter drops it.  ``__time__`` is the read clock's (epoch None).
"""

from __future__ import annotations

import json
import re


class JsonFilter:
    def __init__(self, params: dict):
        self.include = {k: re.compile(p) for k, p in
                        (params.get("include") or {}).items()}

    def expected(self, line: bytes):
        """``(record, None)`` the deployment must emit for one input line
        (newline stripped), or ``None`` when the deployment drops the line."""
        try:
            obj = json.loads(line)
        except ValueError:
            obj = None
        if isinstance(obj, dict):
            rec = {k: v if isinstance(v, str) else json.dumps(
                v, ensure_ascii=False, separators=(",", ":"))
                for k, v in obj.items()}
        else:
            rec = {"rawLog": line.decode("utf-8", "replace")}
        for key, rx in self.include.items():
            if key not in rec or rx.fullmatch(rec[key]) is None:
                return None
        return rec, None


def make(params: dict) -> JsonFilter:
    return JsonFilter(params)

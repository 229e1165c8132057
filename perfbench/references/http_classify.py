"""Plain reference ``http_classify``: regex parse of an HTTP event record, then
an ordered rule list over its path, one line at a time with Python's ``re``.

It imports nothing of the program and reads nothing the program made.  Its
parameters are the ``reference`` object of a configuration's ``config.json``:

    regex       the parse pattern; a line is parsed when the WHOLE line matches
    keys        one per capture
    source_key  the parsed field the rules read
    target_key  the field the category is written to
    default     the category of a value no rule matches
    rules       [{"name": ..., "regex": ...}], in the order the deployment
                tries them: this file's own copy of the list

For a parsed line, ``re.fullmatch`` of each rule on the source field in order;
the first that matches names the category, else ``default``.  A line the parse
regex rejects is kept whole under ``rawLog`` (the processor's
KeepingSourceWhenParseFail default) and, having no source field, gets no
category.  ``__time__`` is the read clock's (epoch None): the deployment has
no timestamp processor.
"""

from __future__ import annotations

import re


class HttpClassify:
    def __init__(self, params: dict):
        self.rx = re.compile(params["regex"].encode("latin-1"))
        self.keys = list(params["keys"])
        self.source_key = params["source_key"]
        self.target_key = params["target_key"]
        self.default = params["default"]
        self.rules = [(r["name"], re.compile(r["regex"]))
                      for r in params["rules"]]

    def category_of(self, value: str) -> str:
        """The first rule's name that fully matches ``value``, else the
        default."""
        for name, rx in self.rules:
            if rx.fullmatch(value) is not None:
                return name
        return self.default

    def expected(self, line: bytes):
        """``(record, None)`` the deployment must emit for one input line
        (newline stripped)."""
        m = self.rx.fullmatch(line)
        if m is None:
            return {"rawLog": line.decode("latin-1")}, None
        rec = {k: g.decode("latin-1") for k, g in zip(self.keys, m.groups())}
        rec[self.target_key] = self.category_of(rec[self.source_key])
        return rec, None


def make(params: dict) -> HttpClassify:
    return HttpClassify(params)

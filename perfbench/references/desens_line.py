"""Plain reference ``desens_line``: ``line_pipeline``'s regex parse and timestamp
parse, then upstream's credential masking on one parsed field, one line at a
time with Python's ``re``.

It imports nothing of the program and reads nothing the program made (it
loads its sibling reference ``line_pipeline`` and leaves it as it is).  Its
parameters are the ``reference`` object of a configuration's ``config.json``:
``line_pipeline``'s (regex, keys, time_key, time_format), and

    source_key    the parsed field that is masked
    before        ContentPatternBeforeReplacedString
    content       ReplacedContentPattern
    replacing     ReplacingString
    replace_all   ReplacingAll: every match, or the first alone

On the field, ``re.sub(b"(" + before + b")" + content, ...)``: each match
becomes its group 1 followed by ``replacing`` (taken literally), leftmost
first, non-overlapping (``count=0``, or 1 where ``replace_all`` is false).  A
line the parse regex rejects has no such field and is kept whole, unmasked,
under ``rawLog`` — upstream's behaviour with ``SourceKey`` a parsed field.
"""

from __future__ import annotations

import re

from benchlib import spec

_lines = spec.load_module("references", "line_pipeline")


class DesensLine:
    def __init__(self, params: dict):
        self.parse = _lines.make(params)
        self.source_key = params["source_key"]
        self.rx = re.compile(b"(" + params["before"].encode("latin-1") + b")"
                             + params["content"].encode("latin-1"))
        self.replacing = params["replacing"].encode("latin-1")
        self.count = 0 if params["replace_all"] else 1

    def mask(self, value: str) -> str:
        return self.rx.sub(lambda m: m.group(1) + self.replacing,
                           value.encode("latin-1"),
                           count=self.count).decode("latin-1")

    def expected(self, line: bytes):
        """``(record, epoch)`` the deployment must emit for one input line
        (newline stripped), or None for a dropped line."""
        got = self.parse.expected(line)
        if got is None:
            return None
        rec, epoch = got
        if self.source_key in rec:
            rec[self.source_key] = self.mask(rec[self.source_key])
        return rec, epoch


def make(params: dict) -> DesensLine:
    return DesensLine(params)

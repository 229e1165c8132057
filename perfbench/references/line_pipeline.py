"""Plain reference ``line_pipeline``: regex parse → optional include-filter →
timestamp parse, one line at a time with Python's ``re`` and ``time``.

It imports nothing of the program and reads nothing the program made.  Its
parameters are the ``reference`` object of a configuration's ``config.json``:

    regex        the pattern; a line is parsed when the WHOLE line matches
    keys         one per capture
    time_key     capture that holds the timestamp
    time_format  strptime format; ``%z`` is matched, not applied, and the stamp
                 is read in the machine's local time (upstream's behaviour
                 without SourceTimezone)
    include      optional {key: pattern}: a parsed record is kept when every
                 named field fully matches; a record without the field (a line
                 the regex rejected) is dropped, as upstream's
                 processor_filter_native does

A line the regex rejects is kept whole under ``rawLog`` (the processor's
KeepingSourceWhenParseFail default) with ``__time__`` left to the read clock.
"""

from __future__ import annotations

import re
import time


class LinePipeline:
    def __init__(self, params: dict):
        self.rx = re.compile(params["regex"].encode("latin-1"))
        self.keys = list(params["keys"])
        self.time_key = params["time_key"]
        self.time_format = params["time_format"]
        self.include = {k: re.compile(p) for k, p in
                        (params.get("include") or {}).items()}
        self._epochs: dict = {}

    def _epoch(self, stamp: str) -> int:
        t = self._epochs.get(stamp)
        if t is None:
            t = int(time.mktime(time.strptime(stamp, self.time_format)))
            self._epochs[stamp] = t
        return t

    def expected(self, line: bytes):
        """``(record, epoch)`` the deployment must emit for one input line
        (newline stripped), or ``None`` when the deployment drops the line.
        ``epoch`` is None where ``__time__`` is the read clock's."""
        m = self.rx.fullmatch(line)
        if m is None:
            rec, epoch = {"rawLog": line.decode("latin-1")}, None
        else:
            rec = {k: g.decode("latin-1") for k, g in zip(self.keys, m.groups())}
            epoch = self._epoch(rec[self.time_key])
        for key, rx in self.include.items():
            if key not in rec or rx.fullmatch(rec[key]) is None:
                return None
        return rec, epoch


def make(params: dict) -> LinePipeline:
    return LinePipeline(params)

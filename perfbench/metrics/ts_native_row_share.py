"""ts_native_row_share — processors: rows of the time column that the timestamp processor's ONE
native call a group (`lct_timestamp_column`) proved and stored, over the rows of the groups that took
the column path, between the two scrapes (/debug/status parse, the labels that start with
processor_parse_timestamp_native: `native_rows` over `rows`).  Nothing where the program has no such
field (a program before the call, a process without the native library), or no row in the window."""

PROCESSOR = "processor_parse_timestamp_native"


def _counts(status):
    """(rows, native rows) summed over the processor's labels; native rows None where none has the field."""
    docs = [doc for label, doc in ((status or {}).get("parse") or {}).items()
            if label.startswith(PROCESSOR)]
    native = [d["native_rows"] for d in docs if "native_rows" in d]
    return sum(d.get("rows", 0) for d in docs), sum(native) if native else None


def read(obs):
    rows1, native1 = _counts(obs["status1"])
    if native1 is None:
        return None
    rows0, native0 = _counts(obs["status0"])
    rows = rows1 - rows0
    return (native1 - (native0 or 0)) / rows if rows > 0 else None

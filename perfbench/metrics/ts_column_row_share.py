"""ts_column_row_share — processors: rows of the time column that the timestamp processor's
column path proved and stored at once, over the rows of the groups that took it: 1 less the rows
handed on to the per-row path over all rows, between the two scrapes (/debug/status parse, the
labels that start with processor_parse_timestamp_native; groups under the processor's crossover
are counted in neither).  Nothing where the program has no such label, or no row in the window."""

PROCESSOR = "processor_parse_timestamp_native"


def _counts(status):
    docs = [doc for label, doc in ((status or {}).get("parse") or {}).items()
            if label.startswith(PROCESSOR)]
    if not docs:
        return None
    return sum(d.get("rows", 0) for d in docs), sum(d.get("fallback_rows", 0) for d in docs)


def read(obs):
    later = _counts(obs["status1"])
    if later is None:
        return None
    rows0, fallback0 = _counts(obs["status0"]) or (0, 0)
    rows = later[0] - rows0
    return 1.0 - (later[1] - fallback0) / rows if rows > 0 else None

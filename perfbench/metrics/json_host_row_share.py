"""json_host_row_share — dispatch: rows the json_fields stage handed to the host's emitter (a
string with an escape, a shape it cannot prove, not an object, an overlong group) over the rows
through the stage, between the two scrapes (/debug/status stage_fusion json).  Nothing where the
program has no such stage or no row went through it."""


def _counts(status):
    doc = ((status or {}).get("stage_fusion") or {}).get("json") or {}
    return doc.get("rows_total", 0), sum((doc.get("host_rows_total") or {}).values())


def read(obs):
    if "json" not in ((obs["status1"] or {}).get("stage_fusion") or {}):
        return None
    rows0, host0 = _counts(obs["status0"])
    rows1, host1 = _counts(obs["status1"])
    return (host1 - host0) / (rows1 - rows0) if rows1 > rows0 else None

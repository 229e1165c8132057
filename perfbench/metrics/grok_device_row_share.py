"""grok_device_row_share — routing: rows whose member's extract crossed the device over all rows
processor_grok saw, between the two scrapes (/debug/status grok: device_rows_total over
rows_total, summed over pipelines).  Under 1: members whose subset is under the routing
crossover run on the native walker, rows no member matches run nowhere, and a probe that read
slow keeps every subset on the host.  Nothing on a program without the section or with no row in
the window."""

from benchlib import spans

KEY = "device_rows_total"


def read(obs, key=KEY):
    later = (obs.get("status1") or {}).get("grok")
    if not later:
        return None
    earlier = (obs.get("status0") or {}).get("grok") or {}

    def total(doc, name):
        return sum(int(row.get(name, 0)) for row in doc.values())
    rows = total(later, "rows_total") - total(earlier, "rows_total")
    part = total(later, key) - total(earlier, key)
    if key == KEY:
        spans.say("grok at the window's end (/debug/status grok)", later)
    return part / rows if rows > 0 else None

"""classify_device_row_share — routing: rows the fused program's label stage labelled over all rows
processor_classify_url_tpu saw, between the two scrapes (/debug/status classify_url:
label_program_rows_total over rows_total, summed over pipelines).  Under 1: a row without the source
field is nobody's (the 1 % of lines the parse regex rejects), a group fusion cannot take and a
drain's partial group under the routing crossover are labelled by the host's scanner.  Nothing on a
program without the section or with no row in the window."""

from benchlib import spans

KEY = "label_program_rows_total"


def read(obs):
    later = (obs.get("status1") or {}).get("classify_url")
    if not later:
        return None
    earlier = (obs.get("status0") or {}).get("classify_url") or {}

    def total(doc, name):
        return sum(int(row.get(name, 0)) for row in doc.values())
    rows = total(later, "rows_total") - total(earlier, "rows_total")
    spans.say("classify_url at the window's end (/debug/status classify_url)", later)
    return (total(later, KEY) - total(earlier, KEY)) / rows if rows > 0 else None

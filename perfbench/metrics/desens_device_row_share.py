"""desens_device_row_share — routing: rows the fused program's mask stage decided over all rows
processor_desensitize_native saw, between the two scrapes (/debug/status desensitize:
mask_program_rows_total over rows_total, summed over pipelines).  Under 1: a row without the field is
nobody's (the 1 % of lines the parse regex rejects), and a row with more matches than the stage's
slots, a byte >= 0x80, a demoted chunk or a group no fused program ran is the host's
(host_rows_total, by reason).  Nothing on a program without the section or with no row in the
window."""

from benchlib import spans

KEY = "mask_program_rows_total"


def read(obs):
    later = (obs.get("status1") or {}).get("desensitize")
    if not later:
        return None
    earlier = (obs.get("status0") or {}).get("desensitize") or {}

    def total(doc, name):
        return sum(int(row.get(name, 0)) for row in doc.values())
    rows = total(later, "rows_total") - total(earlier, "rows_total")
    spans.say("desensitize at the window's end (/debug/status desensitize)", later)
    return (total(later, KEY) - total(earlier, KEY)) / rows if rows > 0 else None

"""ml_device_line_share — routing: physical lines the start-pattern classify sent through the
device over all lines it classified, between the two scrapes (/debug/status multiline:
device_lines_total over lines_total, summed over pipelines).  Under 1: the routing probe kept
groups on the host walker, or rows over 4,096 bytes took re.  Nothing on a program without the
section or with no line in the window."""

from benchlib import spans


def read(obs):
    later = (obs.get("status1") or {}).get("multiline")
    if not later:
        return None
    earlier = (obs.get("status0") or {}).get("multiline") or {}

    def total(doc, key):
        return sum(int(row.get(key, 0)) for row in doc.values())
    lines = total(later, "lines_total") - total(earlier, "lines_total")
    device = total(later, "device_lines_total") - total(earlier, "device_lines_total")
    spans.say("multiline at the window's end (/debug/status multiline)", later)
    return device / lines if lines > 0 else None

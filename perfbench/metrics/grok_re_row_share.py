"""grok_re_row_share — routing: rows that met Python's re (a CPU-tier member, a row over 4,096
bytes) over all rows processor_grok saw, between the two scrapes (/debug/status grok:
re_rows_total over rows_total).  0.0 while every member of the list is on the SEGMENT tier; above
it, per-row work under the interpreter lock is inside the window.  Nothing on a program without
the section or with no row in the window."""

from benchlib import spec


def read(obs):
    return spec.load_module("metrics", "grok_device_row_share").read(
        obs, key="re_rows_total")

"""grok_list_program_row_share — routing: rows that rode the one-program dispatch of the whole
Match list over all rows processor_grok saw, between the two scrapes (/debug/status grok:
list_program_rows_total over rows_total).  Under 1: groups under the routing crossover, lists the
program cannot hold and a sick lane's recovery take the per-member path.  Nothing on a program
without the section or with no row in the window."""

from benchlib import spec


def read(obs):
    return spec.load_module("metrics", "grok_device_row_share").read(
        obs, key="list_program_rows_total")

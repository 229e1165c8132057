"""throttled_round_share — governor: file-server rounds whose sleep the CPU governor stretched
(x3 or x8), over all rounds, between the two scrapes of /debug/status file_input."""

from benchlib import spans


def read(obs):
    return spans.throttled_round_share(obs)

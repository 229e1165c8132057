"""pause_share — host: seconds in runtime.gc, checkpoint.dump, ledger.audit and
self_monitor.tick spans (loong_span_seconds, later scrape less earlier) over the seconds
between the scrapes.  Reported as pause_share.sat and pause_share.tail."""

from benchlib import spans


def read(obs):
    return spans.pause_share(obs)

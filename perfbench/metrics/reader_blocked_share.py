"""reader_blocked_share — file input: reads the file server did not make (queue over its high
watermark) or took back (push rejected), over those and the reads it made, between the two
scrapes of /debug/status file_input.  Near 1 in a closed loop: downstream sets the pace.
Reported as reader_blocked_share.sat and reader_blocked_share.tail."""

from benchlib import spans


def read(obs):
    return spans.reader_blocked_share(obs)

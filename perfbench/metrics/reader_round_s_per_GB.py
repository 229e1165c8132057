"""reader_round_s_per_GB — file input: seconds in the input.file.round spans (a round of the file
server's thread that read a group: discovery, the files' stats, and for each group the read, the
push to the queue and the checkpoint) per GB delivered in the traced slice.  Says, on standard
error, what the rounds are made of, seconds and CPU seconds.  Nothing on a program without the
span."""

from benchlib import spans, threads


def read(obs):
    value = spans.per_GB_in_slice(obs, ("input.file.round",))
    if value is not None:
        spans.say("input.file.round in the slice: [seconds, CPU seconds] of the rounds, of "
                  "their self time and of their children",
                  threads.makeup_cpu(obs, "input.file.round"))
    return value

"""worker_offcpu_share — host: over the worker thread's spans in the traced slice (the thread most
processor.* spans ran on), 1 − Σ self cpu_s / Σ self seconds: the part of the worker's accounted
wall time it spent off a CPU (the interpreter lock, the device, a full FIFO, the host's run
queue).  Spans that record no cpu_s (stopwatches: pipeline.process while its group is in flight,
device.roundtrip) are left out of both sums.  Says, on standard error, the worker's account by
span and the device's idle gaps by thread.  Nothing on a program whose spans carry no cpu_s."""

from benchlib import spans, threads


def read(obs):
    value = threads.worker_offcpu_share(obs)
    if value is not None:
        # [seconds, CPU seconds, spans, spans without a reading] by name; only flusher.serialize may
        # be here (it carries the CPU of the one native call that flusher.write is the other half of)
        spans.say("span names whose CPU seconds pass their wall seconds by over 1 %",
                  {n: row for n, row in threads.cpu_by_name(obs).items()
                   if row[1] > row[0] * 1.01})
    gaps = threads.idle_gaps_by_thread(obs)
    if gaps is not None:
        spans.say("device idle seconds of the slice by what each host thread was doing "
                  "(one column a thread)", gaps)
    return value

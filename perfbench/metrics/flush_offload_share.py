"""flush_offload_share — serialize / sink: batches whose serialize and write ran on a write-through
sink's own sender thread (/debug/status flush, offloaded_total summed over the sinks), over the
batches handed to the sinks (batches_total), between the two scrapes.  Nothing where the program has
no such section, or no batch was handed over in the window."""


def _counts(status):
    sinks = (status or {}).get("flush")
    if not sinks:
        return None
    return (sum(s.get("batches_total", 0) for s in sinks.values()),
            sum(s.get("offloaded_total", 0) for s in sinks.values()))


def read(obs):
    later = _counts(obs["status1"])
    if later is None:
        return None
    batches0, offloaded0 = _counts(obs["status0"]) or (0, 0)
    batches = later[0] - batches0
    if batches <= 0:
        return None
    # read under one lock in the program, but a batch is counted as handed over when it enters the
    # FIFO and as offloaded when its write has landed: those in between at the first scrape land
    # in the window, those at the second have not yet
    return min((later[1] - offloaded0) / batches, 1.0)

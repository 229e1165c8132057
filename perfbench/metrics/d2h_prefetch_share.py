"""d2h_prefetch_share — dispatch: dispatches whose outputs all started their copy back to the
host when the dispatch was issued (/debug/status device d2h_prefetched_total), over all
dispatches (dispatched_total), between the two scrapes.  Nothing where the program has no such
counter, or no dispatch fell in the window."""


def read(obs):
    dev0 = (obs["status0"] or {}).get("device") or {}
    dev1 = (obs["status1"] or {}).get("device") or {}
    if "d2h_prefetched_total" not in dev1:
        return None
    dispatched = dev1.get("dispatched_total", 0) - dev0.get("dispatched_total", 0)
    if dispatched <= 0:
        return None
    started = dev1["d2h_prefetched_total"] - dev0.get("d2h_prefetched_total", 0)
    # read under one lock in the program, but a dispatch is counted as dispatched before its
    # kernel call and as prefetched after it: one in between at a scrape is still a dispatch
    return min(started / dispatched, 1.0)

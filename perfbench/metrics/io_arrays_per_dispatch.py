"""io_arrays_per_dispatch — dispatch: arrays that crossed per dispatch, both ways: those the
dispatches handed to their calls (/debug/status device h2d_arrays_total; each numpy argument of a
jitted call is a host→device transfer of its own) plus the outputs whose copy back they started
(d2h_arrays_total; each is a copy start and an np.asarray of its own), over the dispatches whose
arrays those counts hold (arrays_dispatched_total where the program publishes it, else every
admitted dispatch, dispatched_total), between the two scrapes.  Nothing where the program has no
such counters, or no dispatch fell in the window."""

#: counted in the same step as the arrays; dispatched_total is counted at admission, before the
#: call, so a dispatch in between at a scrape moves the ratio by a ten-thousandth
COUNTED = "arrays_dispatched_total"


def read(obs):
    dev0 = (obs["status0"] or {}).get("device") or {}
    dev1 = (obs["status1"] or {}).get("device") or {}
    if "h2d_arrays_total" not in dev1 or "d2h_arrays_total" not in dev1:
        return None
    key = COUNTED if COUNTED in dev1 else "dispatched_total"
    dispatched = dev1.get(key, 0) - dev0.get(key, 0)
    if dispatched <= 0:
        return None
    return ((dev1["h2d_arrays_total"] - dev0.get("h2d_arrays_total", 0))
            + (dev1["d2h_arrays_total"] - dev0.get("d2h_arrays_total", 0))) / dispatched

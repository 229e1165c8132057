#!/usr/bin/env python3
"""xplane_dump.py <trace.xplane.pb> <events.json> — the profiler's trace as
plain events.  Run as a process of its own with JAX_PLATFORMS=cpu, after the
agent has exited: reading the file needs jax's parser, and the benchmark's
parent never imports jax.  Writes {"names": [...], "events": [[plane, line,
name index, start_ns, duration_ns], ...]} for the device planes and the
launcher's mark."""

import json
import sys


def main(argv) -> int:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(argv[1])
    names: dict = {}
    events = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if device or ev.name == "perfbench_mark":
                    idx = names.setdefault(ev.name, len(names))
                    events.append([plane.name, line.name, idx,
                                   ev.start_ns, ev.duration_ns])
    with open(argv[2], "w") as f:
        json.dump({"names": list(names), "events": events}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

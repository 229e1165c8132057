"""What the benchmark's tests share to hold a cell by what is there: the
per-layer entries every other saturated cell reports, and the plugins a cell's
pipeline runs.  Never the benchmark's size, an entry's place, or another cell's
exact list, so that a later cell is new files and appended entries alone.
The test module puts ``perfbench`` on ``sys.path`` before importing this."""

import os
import re

from benchlib import spec


def common_of_the_other_backlog_cells(bm: dict, cell: str) -> set:
    """The per-layer entries that every other cell reporting delivered_MBps
    reports: what a saturated cell's reader layers give everywhere."""
    return set.intersection(*(
        {m["name"] for m in spec.metrics_of_cell(bm, w["name"], "per_layer")}
        for w in bm["workloads"] if w["name"] != cell and "delivered_MBps"
        in {m["name"] for m in spec.metrics_of_cell(bm, w["name"],
                                                     "end_to_end")}))


def plugins(bm: dict, cell: str) -> set:
    """The plugin types of the cell's pipeline file."""
    cfg = spec.load_config(bm, spec.find_cell(bm, cell)["config"])
    with open(os.path.join(cfg["dir"], cfg["pipeline"])) as fh:
        return set(re.findall(r"Type: (\w+)", fh.read()))

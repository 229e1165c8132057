"""Where the benchmark's data files are, and how a name finds its file.

Everything that belongs to one configuration, one traffic mix, one line
source, one plain reference or one metric sits in a file of its own, found by
the name ``BENCHMARK.json`` (or a configuration's ``config.json``) gives it.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """The benchmark's data files do not describe a runnable cell."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SpecError(f"no BENCHMARK.json in {root}")
    return load_json(path)


def find_cell(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"BENCHMARK.json has no workload {name!r}; it has "
                    f"{[w['name'] for w in bm['workloads']]}")


def load_config(bm: dict, name: str, root: str = ROOT) -> dict:
    """A configuration's ``config.json`` with ``dir`` (its directory) added."""
    for c in bm["configs"]:
        if c["name"] == name:
            path = os.path.join(root, c["file"])
            doc = load_json(path)
            doc["dir"] = os.path.dirname(path)
            return doc
    raise SpecError(f"BENCHMARK.json has no configuration {name!r}")


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    path = os.path.join(bench_dir, "traffic", name + ".json")
    if not os.path.exists(path):
        raise SpecError(f"no traffic file {path}")
    return load_json(path)


def load_peaks(bench_dir: str = BENCH_DIR) -> dict:
    return load_json(os.path.join(bench_dir, "peaks.json"))


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``<bench_dir>/<kind>/<name>.py`` as a module (kind: sources,
    references, metrics).  A name with a suffix (``device_row_share.sat``:
    one quantity under a name for each end-to-end metric it moves) that has
    no file of its own is read by the file of the name before the first
    ``.`` — one reader, never a copy of it."""
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(bench_dir, kind, name.split(".", 1)[0] + ".py")
    if not os.path.exists(path):
        raise SpecError(f"no {kind} file for {name!r} under "
                        f"{os.path.join(bench_dir, kind)}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of_cell(bm: dict, cell: str, section: str) -> list:
    """The metrics of ``section`` (end_to_end / per_layer) this cell reports.
    A metric without a ``workloads`` key is every cell's that reports the
    end-to-end metric it moves (an end-to-end metric: every cell's)."""
    e2e_here = {m["name"] for m in bm["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]}
    if section == "end_to_end":
        return [m for m in bm["end_to_end"] if m["name"] in e2e_here]
    return [m for m in bm["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e_here)]

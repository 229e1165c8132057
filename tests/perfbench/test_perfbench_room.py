"""Room for the next deployment: a later PR adds a configuration, a cell and a
per-layer metric as new files and appended entries alone, so no test here
may hold the benchmark's size, an entry's place, or another PR's lists.

Two guards.  An ``ast`` scan of every test file of the benchmark fails on a
comparison with ``len(BM[...])``, on a negative index or slice of
``BM["per_layer" | "configs" | "workloads"]`` (or of a list made from one),
on such a list held whole by ``==``, and on a ``"workloads"`` compared by
``==`` — a list held whole, or an entry held whole with its list — each form
shown caught on a made-up source.  And a
copy of the benchmark with an eighth cell, a seventh configuration and a new
entry, added the way such a PR adds them, passes every structural test."""

import ast
import glob
import json
import os
import re
import shutil
import subprocess
import sys

# the benchmark's library lives beside the benchmark, not in the program
sys.path.append(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench"))

import pytest

from benchlib import spec

REPO = spec.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
LISTS = ("per_layer", "configs", "workloads")
#: the tests that read BENCHMARK.json, the entries and the configurations —
#: no whole cell run among them
STRUCTURAL = ("entries_are or the_entry_is or seven_entries or entries_alone "
              "or every_entry or asks_nothing or the_traffic_and_the_cell "
              "or backlog_cells_report or contract or every_name "
              "or each_cell_reports or pins_its_state")


# -- the scan ---------------------------------------------------------------------------

def _is_bm(node, key=None) -> bool:
    """``BM[...]``, or ``BM[key]`` where a key is given."""
    return isinstance(node, ast.Subscript) \
        and isinstance(node.value, ast.Name) and node.value.id == "BM" and (
            key is None or (isinstance(node.slice, ast.Constant)
                            and node.slice.value in key))


def _from_a_list(node) -> bool:
    """``BM["per_layer"]`` and the like, or a comprehension over one."""
    if _is_bm(node, LISTS):
        return True
    return isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)) \
        and any(_is_bm(g.iter, LISTS) for g in node.generators)


def _negative(node) -> bool:
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return True
    if isinstance(node, ast.Slice):
        return any(_negative(b) for b in (node.lower, node.upper)
                   if b is not None)
    return False


def _holds_workloads(node) -> bool:
    """``x["workloads"]``, an entry literal with a ``"workloads"`` key, or a
    list of such entries."""
    if isinstance(node, ast.Subscript) \
            and isinstance(node.slice, ast.Constant) \
            and node.slice.value == "workloads":
        return True
    if isinstance(node, ast.Dict):
        return any(isinstance(k, ast.Constant) and k.value == "workloads"
                   for k in node.keys)
    if isinstance(node, (ast.List, ast.Tuple)):
        return any(_holds_workloads(e) for e in node.elts)
    return False


def pins(source: str) -> list:
    """(line, what) for every pin of the benchmark's state in ``source``."""
    found = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Compare):
            operands = [node.left] + node.comparators
            for op in operands:
                if isinstance(op, ast.Call) and isinstance(op.func, ast.Name) \
                        and op.func.id == "len" and op.args \
                        and _is_bm(op.args[0]):
                    found.append((node.lineno, "a count of the benchmark"))
            if any(isinstance(o, (ast.Eq, ast.NotEq)) for o in node.ops):
                if any(_holds_workloads(op) for op in operands):
                    found.append((node.lineno, "workloads held by =="))
                if any(_from_a_list(op) for op in operands):
                    found.append((node.lineno, "a list of the benchmark held "
                                               "by =="))
        if isinstance(node, ast.Subscript) and _negative(node.slice) \
                and _from_a_list(node.value):
            found.append((node.lineno, "an entry by its place"))
    return found


@pytest.mark.parametrize("source,what", [
    ('assert len(BM["workloads"]) == 7', "a count of the benchmark"),
    ('assert 6 == len(BM["configs"])', "a count of the benchmark"),
    ('entry = BM["per_layer"][-1]', "an entry by its place"),
    ('assert BM["workloads"][-1] == cell', "an entry by its place"),
    ('last = BM["configs"][-2]["name"]', "an entry by its place"),
    ('assert [m["name"] for m in BM["per_layer"]][-3:] == READERS',
     "an entry by its place"),
    ('tail = BM["per_layer"][-4:]', "an entry by its place"),
    ('assert m["workloads"] == [CELL]', "workloads held by =="),
    ('assert by_name[n]["workloads"] == CELLS', "workloads held by =="),
    ('assert by_name[n]["workloads"] != ["a", "b"]', "workloads held by =="),
    ('assert entries == [{"name": NAME, "workloads": CELLS}]',
     "workloads held by =="),
    ('assert m == {"name": name, "unit": unit, "workloads": cells}',
     "workloads held by =="),
    ('assert [w["name"] for w in BM["workloads"] if w["traffic"] == "backlog"]'
     ' == SAT', "a list of the benchmark held by =="),
    ('assert {c["name"] for c in BM["configs"]} == {"a", "b"}',
     "a list of the benchmark held by =="),
], ids=["len_left", "len_right", "last_entry", "last_cell", "second_config",
        "names_tail", "entries_tail", "list_literal", "module_constant",
        "not_equal", "entries_whole", "entry_whole", "cells_of_a_mix",
        "config_names"])
def test_the_scan_catches_each_form_of_a_pin(source, what):
    assert [w for _line, w in pins(source)] == [what]


@pytest.mark.parametrize("source", [
    '(entry,) = [m for m in BM["per_layer"] if m["name"] == NAME]',
    'assert set(CELLS) <= set(entry["workloads"])',
    'assert CELL in m["workloads"]',
    'assert {k: v for k, v in m.items() if k != "workloads"} == {"name": N}',
    'four = sum(1 for w in BM["workloads"] if w["chips"] == 4)\n'
    'assert four <= max(1, len(BM["workloads"]) // 2)',
    'assert r.stdout.strip().splitlines()[-1]',
    'assert doc["checks"][-1]',
    'for cell in SAT:\n    assert spec.find_cell(BM, cell)["traffic"] == "b"',
    'assert [m["name"] for m in BM["per_layer"]].count(name) == 1',
], ids=["by_name", "superset", "member", "fields_but_workloads",
        "four_chip_share", "last_line", "other_negative", "cells_of_a_mix",
        "once_by_name"])
def test_the_scan_lets_what_holds_by_name_through(source):
    assert pins(source) == []


def test_no_test_of_the_benchmark_pins_its_state():
    files = sorted(glob.glob(os.path.join(HERE, "*.py")))
    assert os.path.abspath(__file__) in files and len(files) > 10
    found = {}
    for path in files:
        with open(path) as fh:
            hits = pins(fh.read())
        if hits:
            found[os.path.basename(path)] = hits
    assert not found, found


# -- the room ---------------------------------------------------------------------------

def _append_a_deployment(root):
    """What a later PR adds: a configuration (a copy of the Apache one's
    directory), a cell on the ``backlog`` mix, a per-layer metric with a
    reader of its own, and the cell on delivered_MBps and on every per-layer
    list that lists every ``backlog`` cell."""
    bench = os.path.join(root, "perfbench")
    shutil.copytree(os.path.join(bench, "configs", "file_regex_apache_512"),
                    os.path.join(bench, "configs", "file_room_probe"))
    cfg_path = os.path.join(bench, "configs", "file_room_probe", "config.json")
    cfg = spec.load_json(cfg_path)
    cfg["name"] = "file_room_probe"
    with open(cfg_path, "w") as fh:
        json.dump(cfg, fh)
    shutil.copy(os.path.join(bench, "metrics", "device_idle_share.py"),
                os.path.join(bench, "metrics", "room_probe_idle_share.py"))
    bm = spec.load_benchmark(root)
    backlog = [w["name"] for w in bm["workloads"] if w["traffic"] == "backlog"]
    assert backlog
    bm["configs"].append({
        "name": "file_room_probe", "source": "a copy of the Apache deployment",
        "file": "perfbench/configs/file_room_probe/config.json",
        "reduced": [], "why": "a later PR's configuration"})
    bm["workloads"].append({
        "name": "room_probe.backlog", "config": "file_room_probe",
        "traffic": "backlog", "chips": 1, "why": "a later PR's cell"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m and (m["name"] == "delivered_MBps" or (
                m in bm["per_layer"] and set(backlog) <= set(m["workloads"]))):
            m["workloads"].append("room_probe.backlog")
    bm["per_layer"].append({
        "name": "room_probe_idle_share", "unit": "share", "better": "lower",
        "source": "device_trace", "layer": "device",
        "moves": "delivered_MBps", "workloads": ["room_probe.backlog"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bm, fh, indent=1)
    return bm


def test_a_new_cell_config_and_entry_pass_every_structural_test(tmp_path):
    ignore = shutil.ignore_patterns("__pycache__", "*.pyc")
    for name in ("perfbench", "example_config"):
        shutil.copytree(os.path.join(REPO, name), tmp_path / name,
                        ignore=ignore)
    shutil.copytree(HERE, tmp_path / "tests" / "perfbench", ignore=ignore)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    bm = _append_a_deployment(str(tmp_path))
    assert "room_probe.backlog" in {w["name"] for w in bm["workloads"]}
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", os.path.join("tests", "perfbench"),
         "-k", STRUCTURAL],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout[-4000:] + r.stderr[-2000:]
    # every file's structural tests ran, none skipped
    passed = re.search(r"(\d+) passed", r.stdout)
    assert passed and int(passed.group(1)) >= 20 and "skipped" not in r.stdout

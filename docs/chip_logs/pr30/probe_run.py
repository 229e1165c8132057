"""perfbench/run.py with the /debug/status `flush` section echoed to stderr (my chip runs only:
the benchmark's own files are as committed; nothing of the measurement changes)."""
import json
import os
import runpy
import sys

sys.path.insert(0, os.path.join(os.getcwd(), "perfbench"))
from benchlib import agent as agentmod  # noqa: E402

_get = agentmod.Agent.get


def get(self, path):
    doc = _get(self, path)
    if path == "/debug/status" and "flush" in doc:
        print("flush-status", json.dumps(doc["flush"]), file=sys.stderr)
    return doc


agentmod.Agent.get = get
sys.argv[0] = "perfbench/run.py"
runpy.run_path("perfbench/run.py", run_name="__main__")

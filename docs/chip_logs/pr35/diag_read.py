"""diag_read.py <spans.jsonl>: the worker's account from a diag copy's spans (diag_patch.py) — by span
name, self wall time and self CPU time a group on the worker's thread (the thread of the
``processor.processor_grok.dispatch`` spans), children subtracted by parent id."""
import collections
import json
import sys

spans = [json.loads(ln) for ln in open(sys.argv[1])]
by_id = {s[3]: s for s in spans}
kids = collections.defaultdict(list)
for s in spans:
    if s[4] in by_id:
        kids[s[4]].append(s)
stage = [s for s in spans if s[0] == "processor.processor_grok.dispatch"]
tids = collections.Counter(s[5].get("tid") for s in stage)
worker = tids.most_common(1)[0][0]
groups = len(stage)
t0 = min(s[1] for s in stage)
t1 = max(s[1] + s[2] for s in stage)
rows = collections.defaultdict(lambda: [0, 0.0, 0.0])
for s in spans:
    if s[5].get("tid") != worker or not (t0 <= s[1] <= t1):
        continue
    wall = s[2] - sum(k[2] for k in kids[s[3]] if k[5].get("tid") == worker)
    cpu = s[5].get("cpu")
    if cpu is not None:
        cpu -= sum(k[5].get("cpu") or 0.0 for k in kids[s[3]] if k[5].get("tid") == worker)
    r = rows[s[0]]
    r[0] += 1
    r[1] += wall
    r[2] += cpu or 0.0
print(f"groups {groups}  stretch {t1 - t0:.3f} s  worker wall a group {(t1 - t0) / groups * 1e3:.3f} ms  "
      f"worker tid {worker}")
covered = 0.0
for name, (n, wall, cpu) in sorted(rows.items(), key=lambda kv: -kv[1][1]):
    if name in ("pipeline.process", "device.roundtrip"):
        continue            # open while the group rides the ring / a stopwatch: no work of the thread
    covered += wall
    print(f"  {name:52s} n {n:5d}  self wall {wall / groups * 1e3:7.3f} ms a group  "
          f"self cpu {cpu / groups * 1e3:7.3f}")
print(f"spans cover {covered / (t1 - t0):.3f} of the worker's wall")

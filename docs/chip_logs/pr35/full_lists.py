"""full_lists.py <checkout>: in a THROW-AWAY copy's BENCHMARK.json, append grok_nginx.backlog to the per-layer
lists that tests of earlier PRs pin (ROADMAP D14), so that a traced run of the copy also reads
io_arrays_per_dispatch, extract_us_per_MiB, extract_roofline and flush_offload_share in the cell.  The
committed BENCHMARK.json is the parent's, byte for byte."""
import json
import sys

path = sys.argv[1] + "/BENCHMARK.json"
bm = json.load(open(path))
for m in bm["per_layer"]:
    if m["name"] in ("io_arrays_per_dispatch", "extract_us_per_MiB", "extract_roofline",
                     "flush_offload_share") and "grok_nginx.backlog" not in m["workloads"]:
        m["workloads"].append("grok_nginx.backlog")
json.dump(bm, open(path, "w"), indent=1)
print("listed the cell in", path)

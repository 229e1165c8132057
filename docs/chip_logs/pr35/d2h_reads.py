"""PR 35: what reading a dispatch's copied-back array costs the host (a measurement script).

The list program's one int32 [1024, 29] output, materialised with np.asarray as the dispatch window does
(copy_to_host_async at submit, np.asarray at the advance): the time of each way of getting the [1024, 14]
offset and length matrices out of it — strided column-block reads straight from the materialised array, or
one sequential copy of the whole block first — each on a FRESH output (a dispatch's array is read once).
Run through the chip tool: python docs/chip_logs/pr35/d2h_reads.py > chiprun_out/d2h_reads.log
"""
import json
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, ".")
import jax  # noqa: E402

REHEARSE = "--rehearse" in sys.argv
REPS = 5 if REHEARSE else 200
B, K = 1024, 14


def med_us(v):
    return round(statistics.median(v) * 1e6, 1)


f = jax.jit(lambda x: x + 1)
x = np.arange(B * (1 + 2 * K), dtype=np.int32).reshape(B, 1 + 2 * K)
origins = np.arange(B, dtype=np.int32)


def fresh():
    y = f(x)
    y.copy_to_host_async()
    y.block_until_ready()
    time.sleep(0.002)
    return np.asarray(y)


rows = {"asarray": [], "strided_len_copy": [], "strided_off_add": [], "member_ge0": [],
        "whole_copy": [], "after_copy_len_copy": [], "after_copy_off_add": [], "after_copy_member_ge0": []}
flags = None
for i in range(REPS):
    y = f(x)
    y.copy_to_host_async()
    y.block_until_ready()
    time.sleep(0.002)
    t = time.perf_counter(); out = np.asarray(y); rows["asarray"].append(time.perf_counter() - t)
    flags = (out.flags["C_CONTIGUOUS"], out.flags["WRITEABLE"], out.flags["OWNDATA"])
    if i % 2 == 0:
        t = time.perf_counter(); a = np.ascontiguousarray(out[:, 1 + K:]); rows["strided_len_copy"].append(time.perf_counter() - t)
        t = time.perf_counter(); b = out[:, 1:1 + K] + origins[:, None]; rows["strided_off_add"].append(time.perf_counter() - t)
        t = time.perf_counter(); c = out[:, 0] >= 0; rows["member_ge0"].append(time.perf_counter() - t)
    else:
        t = time.perf_counter(); o2 = np.array(out); rows["whole_copy"].append(time.perf_counter() - t)
        t = time.perf_counter(); a = np.ascontiguousarray(o2[:, 1 + K:]); rows["after_copy_len_copy"].append(time.perf_counter() - t)
        t = time.perf_counter(); b = o2[:, 1:1 + K] + origins[:, None]; rows["after_copy_off_add"].append(time.perf_counter() - t)
        t = time.perf_counter(); c = o2[:, 0] >= 0; rows["after_copy_member_ge0"].append(time.perf_counter() - t)
print(json.dumps({"device": jax.devices()[0].device_kind, "flags_contig_writeable_owndata": flags,
                  "median_us": {k: med_us(v) for k, v in rows.items()}}))

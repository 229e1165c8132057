"""PR 35, the Match list program alone on the chip (a measurement script, not part of the package).

Over 1,024 seeded rows of the grok cell at 1024 x 512: the list program (every member's extract and the
first-match choice in one module, ops/kernels/match_list.py) beside member 1's own extract program —
the compile (wall seconds, and the process's CPU seconds over them: the cores the compile took), the
cycle host to host through the packed entry (numpy in, np.asarray out), the call with its operand
resident, the device time from a profiler trace of 50 calls, and that the list program equals
re.fullmatch member by member, in Match order, for every row (member index and every span).  Run through
the chip tool from the checkout root:

    python docs/chip_logs/pr35/alone.py [--seed N] > chiprun_out/alone.log

--rehearse: the CPU rehearsal of the control flow (XLA path, 64 rows, few cycles, no trace).
"""

import glob
import json
import os
import re
import statistics
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, ".")
sys.path.insert(0, "perfbench")

REHEARSE = "--rehearse" in sys.argv
SEED = int(sys.argv[sys.argv.index("--seed") + 1]) if "--seed" in sys.argv else 2147499001
REPS = 5 if REHEARSE else 300
B, L = (256, 512) if REHEARSE else (1024, 512)
N = 64 if REHEARSE else 1024

os.environ.setdefault("LOONG_SHARDED", "0")

import jax  # noqa: E402

from benchlib import spec  # noqa: E402
from loongcollector_tpu.ops.device_stream import BatchRing  # noqa: E402
from loongcollector_tpu.ops.regex.engine import get_engine, pallas_by_default  # noqa: E402
from loongcollector_tpu.pipeline.plugin.interface import PluginContext  # noqa: E402
from loongcollector_tpu.processor.grok import ProcessorGrok  # noqa: E402


def say(**row):
    print(json.dumps(row), flush=True)


def med_us(samples):
    return round(statistics.median(samples) * 1e6, 1)


def timed(fn):
    w, c = time.perf_counter(), time.process_time()
    out = fn()
    return out, time.perf_counter() - w, time.process_time() - c


def device_us_per_call(fn, calls):
    """Device microseconds a call: the XLA Ops line of a profiler trace over ``calls`` calls."""
    from jax.profiler import ProfileData
    d = tempfile.mkdtemp(prefix="alone_trace_")
    jax.profiler.start_trace(d)
    for _ in range(calls):
        np.asarray(fn())
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))
    ops, modules = {}, {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            into = ops if line.name == "XLA Ops" else modules if line.name == "XLA Modules" else None
            for ev in line.events if into is not None else ():
                into[ev.name] = into.get(ev.name, 0) + ev.duration_ns
    return ({k: round(v / calls / 1e3, 2) for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:8]},
            {k[:60]: round(v / calls / 1e3, 2) for k, v in modules.items()})


def main():
    bm = spec.load_benchmark()
    cfg = spec.load_config(bm, "file_grok_nginx")
    match = cfg["reference"]["match"]
    source = spec.load_module("sources", cfg["source"]["kind"]).make(cfg["source"], SEED)
    lines = [source.line(j)[:-1] for j in range(N)]
    lens = np.array([len(x) for x in lines], np.int32)
    arena = np.frombuffer(b"".join(lines), np.uint8)
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int64)
    slot = BatchRing().lease(B, L)
    batch = slot.pack(arena, offs, lens)
    say(device=jax.devices()[0].device_kind, platform=jax.devices()[0].platform, seed=SEED,
        rows=N, geometry=f"{B}x{L}", pallas=pallas_by_default())

    p = ProcessorGrok()
    assert p.init({"Match": match}, PluginContext("alone")) and p._list_ok
    kern = p._list_program(None)
    one = p._engines[0][0]._single_device_kernel()

    # -- compile: cold, the process's CPU beside the wall ----------------------------------
    out, wall, cpu = timed(lambda: np.asarray(kern.packed_call(slot.packed)))
    say(compile="list program", wall_s=round(wall, 2), cpu_s=round(cpu, 2), cores=round(cpu / wall, 2))
    out1, wall, cpu = timed(lambda: np.asarray(one.packed_call(slot.packed)))
    say(compile="member 1 alone", wall_s=round(wall, 2), cpu_s=round(cpu, 2), cores=round(cpu / wall, 2))

    # -- equal to re.fullmatch in Match order, member and every span -------------------------
    member, k_off, k_len = kern.unpack(out)
    rxs = [e._re for e, _ in p._engines]
    bad = 0
    taken = [0] * (len(rxs) + 1)
    for r, line in enumerate(lines):
        want_member, want = -1, {}
        for i, rx in enumerate(rxs):
            m = rx.fullmatch(line)
            if m is not None:
                want_member = i
                caps, cols = p._placement[i]
                want = {c: m.span(g + 1) for g, c in zip(caps, cols) if m.span(g + 1)[0] >= 0}
                break
        taken[want_member + 1] += 1
        got = {c: (int(k_off[r, c]), int(k_off[r, c] + k_len[r, c]))
               for c in range(len(p._keys)) if k_len[r, c] >= 0}
        if int(member[r]) != want_member or got != want:
            bad += 1
            if bad <= 3:
                say(differs=r, member=int(member[r]), want_member=want_member, got=got, want=want)
    say(check="list program == re.fullmatch in Match order", rows=N, differing=bad,
        unmatched_and_members=taken)
    ok1, _o, _l = one.unpack(out1)
    say(check="member 1 alone", ok_rows=int(ok1[:N].sum()),
        re_rows=sum(rxs[0].fullmatch(x) is not None for x in lines))

    # -- cycles -------------------------------------------------------------------------------
    for name, k in (("list program", kern), ("member 1 alone", one)):
        for _ in range(5):
            np.asarray(k.packed_call(slot.packed))
        cyc = []
        for _ in range(REPS):
            t = time.perf_counter()
            np.asarray(k.packed_call(slot.packed))
            cyc.append(time.perf_counter() - t)
        x = jax.device_put(slot.packed)
        res = []
        for _ in range(REPS):
            t = time.perf_counter()
            k.packed_call(x).block_until_ready()
            res.append(time.perf_counter() - t)
        say(cycle=name, host_to_host_us=med_us(cyc), least_us=round(min(cyc) * 1e6, 1),
            operand_resident_us=med_us(res), least_resident_us=round(min(res) * 1e6, 1))
        if not REHEARSE:
            ops, modules = device_us_per_call(lambda k=k: k.packed_call(slot.packed), 50)
            say(device=name, us_per_call_by_module=modules, us_per_call_by_op=ops)
    say(bad=bad)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

"""PR 32, the two halves alone on the chip (a measurement script, not part of the package).

What a dispatch's fixed costs are when nothing else runs: the jitted call with two numpy arguments
(rows, lengths) against one packed argument, and N copy starts + N np.asarray against one of each,
at the geometries the cells run.  Each pair is also read beside a busy Python thread, because what
the worker pays in the agent is the hand-over of the interpreter lock at every call that lets go of
it (PERF.md section 6, PR 28 and PR 30).  Run through the chip tool from the checkout root:

    python docs/chip_logs/pr32/halves.py > chiprun_out/halves.log

Times are host-clock microseconds a call, the median of REPS cycles after a warm-up; every cycle
ends in np.asarray of the outputs, so nothing is left in flight between cycles.
"""

import json
import statistics
import sys
import threading
import time

import numpy as np

sys.path.insert(0, ".")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from loongcollector_tpu.ops.device_stream import BatchRing  # noqa: E402
from loongcollector_tpu.ops.kernels.field_extract import MatchKernel  # noqa: E402
from loongcollector_tpu.ops.kernels.field_extract_pallas import PallasExtractKernel  # noqa: E402
from loongcollector_tpu.ops.regex.program import compile_tier1  # noqa: E402

APACHE = r'(\S+) (\S+) (\S+) \[([^\]]+)\] "(\S+) (\S+) ([^"]*)" (\d{3}) (\d+)'
# --rehearse: the CPU rehearsal of the control flow (interpreted Pallas, small shapes, few cycles)
REHEARSE = "--rehearse" in sys.argv
REPS = 5 if REHEARSE else 400


def say(**row):
    print(json.dumps(row), flush=True)


def med_us(samples):
    return round(statistics.median(samples) * 1e6, 1)


def apache_slot(B, L, seed):
    rng = np.random.default_rng(seed)
    lines = []
    for i in range(B - 5):
        pad = "x" * int(rng.integers(L // 2, L - 120))
        lines.append(f'10.0.{i % 256}.{i % 199} - u{i} [02/Oct/2026:10:00:{i % 60:02d} +0000] '
                     f'"GET /p/{i:012d}/{pad} HTTP/1.1" 200 {i}'.encode())
    lines.append(b"not an access line")
    lens = np.array([len(x) for x in lines], np.int32)
    arena = np.frombuffer(b"".join(lines), np.uint8)
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int64)
    slot = BatchRing().lease(B, L)
    return slot, slot.pack(arena, offs, lens)


def cycle_tuple(kern, batch):
    t0 = time.perf_counter()
    outs = kern(batch.rows, batch.lengths)
    t1 = time.perf_counter()
    for o in outs:
        o.copy_to_host_async()
    t2 = time.perf_counter()
    outs[0].block_until_ready()
    t3 = time.perf_counter()
    got = [np.asarray(o) for o in outs]
    t4 = time.perf_counter()
    return got, (t1 - t0, t2 - t1, t4 - t3)


def cycle_packed(kern, slot):
    t0 = time.perf_counter()
    out = kern.packed_call(slot.packed)
    t1 = time.perf_counter()
    out.copy_to_host_async()
    t2 = time.perf_counter()
    out.block_until_ready()
    t3 = time.perf_counter()
    got = kern.unpack(np.asarray(out))
    t4 = time.perf_counter()
    return got, (t1 - t0, t2 - t1, t4 - t3)


def measure(name, fn, *args):
    # beside a busy thread a cycle is a few 5 ms switch intervals: fewer cycles there
    reps = max(REPS // 4, 5) if "busy" in name else REPS
    for _ in range(20 if reps == REPS else 5):
        fn(*args)
    legs = [fn(*args)[1] for _ in range(reps)]
    say(what=name, call_us=med_us([x[0] for x in legs]), copy_start_us=med_us([x[1] for x in legs]),
        asarray_us=med_us([x[2] for x in legs]), host_us=med_us([sum(x) for x in legs]))


class Busy:
    """A Python thread that never lets go of the lock by itself: what the file server's thread and
    the sink's sender are to the worker."""

    def __enter__(self):
        self.stop = False
        self.t = threading.Thread(target=self.spin, daemon=True)
        self.t.start()
        return self

    def spin(self):
        n = 0
        while not self.stop:
            n += 1

    def __exit__(self, *exc):
        self.stop = True
        self.t.join()


def synthetic(B, L, forms):
    """A jitted stand-in with a program's output SHAPES (the fixed costs do not depend on what the
    program computes): forms is a list of widths, None for a [B] output."""
    def outs_of(rows, lengths):
        base = lengths + rows[:, 0].astype(jnp.int32)
        return tuple(base if w is None else jnp.broadcast_to(base[:, None], (B, w)) + 0
                     for w in forms)

    def packed_of(rows, lengths):
        outs = outs_of(rows, lengths)
        return jnp.concatenate([o[:, None] if o.ndim == 1 else o for o in outs], axis=1)

    return jax.jit(outs_of), jax.jit(packed_of)


def d2h_only(name, B, L, forms):
    rows = np.zeros((B, L), np.uint8)
    lengths = np.arange(B, dtype=np.int32)
    f_tuple, f_packed = synthetic(B, L, forms)

    def many():
        outs = f_tuple(rows, lengths)
        outs[0].block_until_ready()
        t0 = time.perf_counter()
        for o in outs:
            o.copy_to_host_async()
        t1 = time.perf_counter()
        got = [np.asarray(o) for o in outs]
        t2 = time.perf_counter()
        return got, (0.0, t1 - t0, t2 - t1)

    def one():
        out = f_packed(rows, lengths)
        out.block_until_ready()
        t0 = time.perf_counter()
        out.copy_to_host_async()
        t1 = time.perf_counter()
        got = np.asarray(out)
        t2 = time.perf_counter()
        return got, (0.0, t1 - t0, t2 - t1)

    width = sum(w or 1 for w in forms)
    measure(f"{name}: {len(forms)} outputs, copy starts + np.asarray", many)
    measure(f"{name}: one [{B}, {width}] output, copy start + np.asarray", one)
    with Busy():
        measure(f"{name}: {len(forms)} outputs, beside a busy thread", many)
        measure(f"{name}: one [{B}, {width}] output, beside a busy thread", one)


def main():
    dev = jax.devices()[0]
    say(device=dev.device_kind, platform=dev.platform, jax=jax.__version__, reps=REPS)
    prog = compile_tier1(APACHE)
    for B, L in ((64, 256), (32, 2048)) if REHEARSE else ((1024, 512), (8192, 256), (256, 2048)):
        slot, batch = apache_slot(B, L, B + L)
        for kern in (PallasExtractKernel(prog, interpret=REHEARSE), MatchKernel(prog)):
            name = f"{type(kern).__name__} {B}x{L}"
            want, _ = cycle_tuple(kern, batch)
            got, _ = cycle_packed(kern, slot)
            same = all(a.dtype == b.dtype and np.array_equal(a, b) for a, b in zip(want, got))
            say(what=name + ": packed entry equals tuple entry on the chip", same=bool(same),
                ok_rows=int(np.asarray(want[0]).astype(bool).sum()), n_real=batch.n_real)
            if not same:
                raise SystemExit("packed entry differs from the tuple entry")
            measure(name + ": two arguments, tuple out", cycle_tuple, kern, batch)
            measure(name + ": one packed argument, one array out", cycle_packed, kern, slot)
            with Busy():
                measure(name + ": two arguments, tuple out, beside a busy thread",
                        cycle_tuple, kern, batch)
                measure(name + ": one packed argument, one array out, beside a busy thread",
                        cycle_packed, kern, slot)
        slot.release()
    if REHEARSE:
        return d2h_only("extract 64x256", 64, 256, [None, 9, 9])
    # the copy back alone, by the shapes of the programs' outputs
    d2h_only("extract 1024x512", 1024, 512, [None, 9, 9])
    d2h_only("extract -> keep 1024x512", 1024, 512, [None, 9, 9, None])
    d2h_only("json_fields -> keep 512x1024", 512, 1024, [None, 17, 17, None, None, 2, None])
    d2h_only("extract 8192x256", 8192, 256, [None, 9, 9])
    d2h_only("extract 256x2048", 256, 2048, [None, 9, 9])


if __name__ == "__main__":
    main()

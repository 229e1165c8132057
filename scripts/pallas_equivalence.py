#!/usr/bin/env python
"""Pallas-vs-XLA extract gate on the chip (run through the chip tool).

Compiles the Mosaic extract kernel and the XLA one for every Tier-1 op
family (the patterns tests/test_pallas_kernel.py fuzzes in the interpreter)
at the geometry the file reader produces (1024x512) and at the largest
length bucket (L = 4096), runs both on seeded rows whose lengths reach L,
and holds them bit for bit to each other and to ``re.fullmatch``.

Two mask-heavy programs follow, reported but not gating: they probe where
``_pick_block_rows``'s estimate stands against what Mosaic accepts at the
32-row floor (ROADMAP S5).

Needs a TPU: the compiled kernel does not exist elsewhere (the interpreter
is tier-1's).  Exit 0 = identical; 1 = a difference or a refused compile in
a gating pattern.
"""

from __future__ import annotations

import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

APACHE = (r'(\S+) (\S+) (\S+) \[([^\]]+)\] '
          r'"(\S+) (\S+) ([^"]*)" (\d{3}) (\d+)')
PATTERNS = [
    APACHE,
    r"(\d+)-(\w+)",
    r"(a+)(?: opt(\d+))? end",                      # optional group
    r"(cat|dog|bird) says (\S+)",                   # alternation
    r"(\d{3}) fixed",                               # counted repeat
    r"pre (.*) post",                               # pivot: ambiguous span
    r"\[([^\]]*)\] (.*)",                           # pivot with class prefix
]
#: distinct classes and literals piled up, to load the VMEM estimate
HEAVY = [
    r"(\d+)-([a-f]+)_([A-Z]+):([g-z]+);([0-9a-f]+)=(\S+) \[([^\]]+)\] "
    r'"([^"]*)" <([^>]*)> (\w+)',
    r"(\d+)-([a-f]+)_([A-Z]+):([g-z]+);([0-9a-f]+)=(\S+) \[([^\]]+)\] "
    r'"([^"]*)" <([^>]*)> \{([^}]*)\} \(([^)]*)\) ([a-m]+)#([n-z]+)@'
    r"([A-M]+)!([N-Z]+)%([0-4]+)&([5-9]+)\*(\w+)",
]
SEEDS = [
    b'1.2.3.4 - frank [10/Oct/2000:13:55:36 -0700] "GET /a HTTP/1.0" 200 23',
    b"123-abc", b"aaa opt7 end", b"aaa end", b"cat says hi", b"dog says x",
    b"421 fixed", b"pre middle bit post", b"[tag] rest of line", b"pre  post",
    b'12-ab_CD:gh;0f=x [t] "q" <a> w',
    b'12-ab_CD:gh;0f=x [t] "q" <a> {b} (c) ab#no@AB!NO%01&56*w',
]
GEOMETRIES = [(1024, 512), (256, 4096), (1024, 4096)]


def stretch(line: bytes, rx, width: int):
    """``line`` grown to ``width`` bytes by repeating one of its own bytes
    where the pattern still matches, or None when no position allows it."""
    for pos in range(len(line) - 1, -1, -1):
        grown = line[:pos] + line[pos:pos + 1] * (width - len(line)) \
            + line[pos:]
        if rx.fullmatch(grown):
            return grown
    return None


def rows_for(pattern: str, B: int, L: int, seed: int):
    """B seeded lines, lengths spread up to L: matching seeds at their own
    and at stretched widths, and printable noise."""
    rng = np.random.default_rng(seed)
    rx = re.compile(pattern.encode())
    good = [s for s in SEEDS if rx.fullmatch(s)]
    for s in list(good):
        for width in (L // 2, L - 1, L):
            g = stretch(s, rx, width)
            if g is not None:
                good.append(g)
    lines = []
    for i in range(B):
        if i % 2 == 0 and good:
            lines.append(good[(i // 2) % len(good)])
        else:
            n = int(rng.integers(1, L + 1))
            lines.append(bytes(rng.integers(32, 127, n, dtype=np.uint8)))
    return lines, rx


def check(pattern: str, B: int, L: int) -> dict:
    from loongcollector_tpu.ops.device_batch import pack_rows
    from loongcollector_tpu.ops.kernels.field_extract import (ExtractKernel,
                                                              walk_masks)
    from loongcollector_tpu.ops.kernels.field_extract_pallas import (
        PallasExtractKernel, _pick_block_rows)
    from loongcollector_tpu.ops.regex.program import compile_tier1
    prog = compile_tier1(pattern)
    span_c, count_c, lits = walk_masks(prog)
    n_masks = len(span_c | count_c) + len(lits)
    lines, rx = rows_for(pattern, B, L, seed=B * 131 + L)
    arena = np.frombuffer(b"".join(lines), dtype=np.uint8)
    lens = np.array([len(ln) for ln in lines], np.int32)
    offs = np.concatenate([[0], np.cumsum(lens[:-1])]).astype(np.int64)
    batch = pack_rows(arena, offs, lens, L)
    assert batch.rows.shape == (B, L), batch.rows.shape

    doc = {"geometry": f"{B}x{L}", "n_masks": n_masks,
           "block_rows": _pick_block_rows(B, L, n_masks)}
    t0 = time.perf_counter()
    ok_x, off_x, len_x = (np.asarray(a) for a in
                          ExtractKernel(prog)(batch.rows, batch.lengths))
    doc["xla_first_call_s"] = round(time.perf_counter() - t0, 2)
    t0 = time.perf_counter()
    try:
        ok_p, off_p, len_p = (np.asarray(a) for a in PallasExtractKernel(
            prog)(batch.rows, batch.lengths))
    except Exception as e:  # noqa: BLE001 — the compiler's words are the result
        doc["pallas"] = "refused"
        doc["message"] = " ".join(str(e).split())[:600]
        return doc
    doc["pallas_first_call_s"] = round(time.perf_counter() - t0, 2)
    doc["pallas"] = "compiled"
    doc["bit_equal_xla"] = bool(
        np.array_equal(ok_x, ok_p) and np.array_equal(off_x, off_p)
        and np.array_equal(len_x, len_p))
    want = np.array([rx.fullmatch(ln) is not None for ln in lines])
    doc["matching_rows"] = int(want.sum())
    doc["re_ok_mismatch"] = int((want != ok_p[:B]).sum())
    return doc


def main() -> int:
    from loongcollector_tpu.ops import device_info
    info = device_info.start()      # places the compile cache; no quiet CPU
    print(f"platform {info['platform']} {info['device_kind']}", flush=True)
    if info["platform"] != "tpu":
        print("pallas_equivalence FAILED: needs a TPU (the compiled Mosaic "
              "kernel exists nowhere else)")
        return 1
    bad = 0
    for gating, patterns in ((True, PATTERNS), (False, HEAVY)):
        for pattern in patterns:
            for B, L in GEOMETRIES:
                doc = check(pattern, B, L)
                fine = (doc["pallas"] == "compiled" and doc["bit_equal_xla"]
                        and doc["re_ok_mismatch"] == 0)
                bad += gating and not fine
                print(f"{'GATE' if gating else 'PROBE'} "
                      f"{'ok  ' if fine else 'DIFF'} {pattern!r} {doc}",
                      flush=True)
    print(f"pallas_equivalence: {'FAILED' if bad else 'OK'} "
          f"({bad} gating failures)")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

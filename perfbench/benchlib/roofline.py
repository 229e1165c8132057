"""The work a kernel call has to do, from its shapes, and the chip's peaks.

Kept with the benchmark so that a kernel rewritten in any way reads against
the same work: nothing here looks at how the kernel is implemented.
"""

from __future__ import annotations

import re


def peak_of(peaks: dict, device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    if device_kind not in peaks:
        raise KeyError(f"peaks.json has no device kind {device_kind!r}; it "
                       f"has {sorted(k for k in peaks if k != 'source')}")
    return peaks[device_kind]


def extract_bytes(rows: int, width: int, captures: int) -> int:
    """Bytes one field-extract call must move: the padded rows in, a length
    per row in, a (start, length) pair of 32-bit words per capture out."""
    return rows * width + 4 * rows + rows * captures * 8


_U8 = re.compile(r"u8\[(\d+),(\d+)\]")
_S32 = re.compile(r"s32\[(\d+),(\d+)\]")


def extract_shapes(op_text: str):
    """(rows, width, captures) of an extract call from the operation's text
    in the profiler trace: the u8[rows,width] operand and the widest
    s32[rows,captures] result.  None when the text names no such shapes."""
    m = _U8.search(op_text)
    if not m:
        return None
    rows, width = int(m.group(1)), int(m.group(2))
    caps = [int(c) for r, c in _S32.findall(op_text) if int(r) == rows]
    if not caps or max(caps) < 2:
        return None
    return rows, width, max(caps)


def hbm_roofline_pct(nbytes: float, seconds: float, peak: dict) -> float:
    """Least time the chip's memory system could take for ``nbytes``, as a
    percentage of the ``seconds`` the kernel took."""
    return 100.0 * (nbytes / (peak["hbm_GBps"] * 1e9)) / seconds

"""Packed dispatch I/O: one buffer in, one buffer out.

A dispatch costs the worker a fixed price per array that crosses — a
host→device transfer for every numpy argument of the jitted call, a
``copy_to_host_async`` start and an ``np.asarray`` for every output —
and each of those lets go of the interpreter lock while the file
server's thread and the sink's sender want it.  The bytes are the small
part.  So the streaming path hands a kernel ONE array and takes ONE back:

* **in** — a ring slot is one contiguous ``u8 [B + ceil(4·B / L), L]``
  array: the first ``B`` rows are the packed rows, the tail holds the
  ``B`` lengths as little-endian int32 (``BatchSlot``,
  ops/device_stream.py).  :func:`split_input` undoes that inside the
  jitted module: a slice and a bitcast in front of the unchanged core.
* **out** — the core's per-row outputs become column ranges of one
  ``int32 [B, K]`` array (:class:`Columns`); the host splits it back
  into the tuple the core returns, as views.

:func:`packed_entry` jits a ``(rows, lengths) -> tuple`` function in
the ``packed -> [B, K]`` form.  A kernel object that offers the pair
``packed_call`` / ``unpack`` is dispatched this way by the window
(``DeviceStream.submit_rows``); one that does not keeps ``(rows,
lengths)`` → tuple.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def packed_rows(B: int, L: int) -> int:
    """Rows of the packed input for a ``[B, L]`` batch: the batch, then
    ``4·B`` bytes of lengths rounded up to whole rows."""
    return B + -(-4 * B // L)


def batch_rows(R: int, L: int) -> int:
    """The ``B`` whose packed input has ``R`` rows of ``L`` bytes
    (``packed_rows`` grows strictly with ``B``, so there is at most one)."""
    B = R * L // (L + 4)
    if B <= 0 or packed_rows(B, L) != R:
        raise ValueError(f"no batch packs into {R} rows of {L} bytes")
    return B


def split_input(packed):
    """Traced: ``packed u8 [R, L]`` -> ``(rows u8 [B, L], lengths i32 [B])``."""
    import jax.numpy as jnp
    from jax import lax
    R, L = packed.shape
    B = batch_rows(R, L)
    tail = packed[B:].reshape(-1)[:4 * B].reshape(B, 4)
    return packed[:B], lax.bitcast_convert_type(tail, jnp.int32)


class Columns:
    """The per-row outputs of a program as column ranges of one
    ``int32 [B, K]`` array.  ``forms`` has one ``(dtype, width)`` per
    output, in the program's order: dtype ``"bool"``, ``"i32"`` or
    ``"u32"``; width ``None`` for a ``[B]`` output, else the ``C`` of a
    ``[B, C]`` one."""

    __slots__ = ("forms", "width")

    def __init__(self, forms: Sequence[Tuple[str, Optional[int]]]):
        self.forms = tuple(forms)
        self.width = sum(w or 1 for _d, w in self.forms)

    def pack(self, outs):
        """Traced: the outputs side by side.  A shape that is not the
        declared one fails the trace — the host's split would be wrong."""
        import jax.numpy as jnp
        from jax import lax
        if len(outs) != len(self.forms):
            raise TypeError(f"{len(outs)} outputs for {len(self.forms)} "
                            f"declared columns")
        cols = []
        for a, (dtype, width) in zip(outs, self.forms):
            if a.ndim != (1 if width is None else 2) \
                    or (width is not None and a.shape[1] != width):
                raise TypeError(f"output {a.shape} is not the declared "
                                f"({dtype}, {width})")
            if a.dtype == jnp.bool_:
                a = a.astype(jnp.int32)
            elif a.dtype == jnp.uint32:
                a = lax.bitcast_convert_type(a, jnp.int32)
            elif a.dtype != jnp.int32:
                raise TypeError(f"output dtype {a.dtype} is no 32-bit "
                                f"column")
            cols.append(a[:, None] if width is None else a)
        return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)

    def unpack(self, out: np.ndarray) -> tuple:
        """Host: the materialised ``[B, K]`` array as the program's tuple
        — views, but for a bool column's compare."""
        if out.ndim != 2 or out.shape[1] != self.width:
            raise ValueError(f"packed output {out.shape} is not "
                             f"[B, {self.width}]")
        split = []
        at = 0
        for dtype, width in self.forms:
            col = out[:, at] if width is None else out[:, at:at + width]
            at += width or 1
            if dtype == "bool":
                col = col != 0
            elif dtype == "u32":
                col = col.view(np.uint32)
            split.append(col)
        return tuple(split)


def span_columns(num_caps: int) -> Tuple[Tuple[str, Optional[int]], ...]:
    """The forms of ``(ok[B], off[B, C], len[B, C])`` — what an extract
    publishes (``C`` is at least 1: a program with no capture still
    writes one column)."""
    C = max(num_caps, 1)
    return (("bool", None), ("i32", C), ("i32", C))


def packed_entry(fn, forms, family: str):
    """``fn(rows, lengths) -> tuple`` as the pair a kernel object offers:
    ``packed_call(packed) -> int32 [B, K]``, jitted under the kernel's own
    ``family`` (the profiler and the compile cache tell the two entries
    of a program apart by their argument shapes, not by name), and
    ``unpack`` for its materialised result.  The slice, the bitcast and
    the concatenate are XLA operations around ``fn`` in the one module;
    ``fn`` itself is unchanged."""
    from .compile_watch import watched_jit
    columns = Columns(forms)

    def packed(buf):
        return columns.pack(fn(*split_input(buf)))
    return watched_jit(packed, family), columns.unpack

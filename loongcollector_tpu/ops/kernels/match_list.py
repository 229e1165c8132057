"""An ordered list of Tier-1 programs as ONE device program.

processor_grok's ``Match`` is a fall-back list: the patterns are tried from
the top and the first that fully matches a row gives the row its fields.
Where every member is on the SEGMENT tier no classify is needed to settle
that order — each member's extract already returns a full-match flag, and
"the first member that fully matches" is the lowest member whose flag is
set.  So the whole list runs as one jitted module over the same ``[B, L]``
rows:

* every member's extract is the existing function, unchanged
  (``build_extract_fn_pallas`` on the chip, ``build_extract_fn`` elsewhere:
  one ``build_extract_core`` walk both), so every differential-fuzz
  guarantee of the lone extract transfers;
* each member's ``[B, C_i]`` spans are placed into the ``K`` columns of the
  union of the members' keys by a static map (absent keys: length -1);
* ``member[b]`` is the lowest ``i`` with ``ok_i[b]`` (-1: none), and the
  spans are selected by it.

One ``int32 [B, 1 + 2K]`` array comes back through the packed entry
(ops/packed_io.py).  The walk, not the tile read, is the extract kernel's
cost (``extract_roofline`` 0.17 % at 13 captures: PERF.md section 6, PR 34),
so one Pallas call a member in the one module is enough; there is no new
kernel body here.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax.numpy as jnp

from ..regex.program import SegmentProgram


def build_match_list_fn(extracts, placement: Sequence[Tuple[Sequence[int],
                                                             Sequence[int]]],
                        num_keys: int):
    """Returns jit-able f(rows u8 [B,L], lengths i32 [B]) ->
    (member i32 [B], off i32 [B,K], len i32 [B,K]).  ``extracts[i]`` is
    member ``i``'s ``(rows, lengths) -> (ok, off, len)``; ``placement[i]``
    its ``(captures, columns)``: capture ``captures[j]`` is the union's
    column ``columns[j]``."""
    i32 = jnp.int32

    def place(i, spans, absent):
        """Member ``i``'s ``[B, C_i]`` in the union's ``K`` columns."""
        column_of = dict(zip(placement[i][1], placement[i][0]))
        fill = jnp.full((spans.shape[0], 1), absent, i32)
        return jnp.concatenate(
            [spans[:, column_of[k]:column_of[k] + 1] if k in column_of
             else fill for k in range(num_keys)], axis=1)

    def match_list(rows, lengths):
        member = off_u = len_u = None
        # from the bottom of the list up, so that the lowest member that
        # matched is the last to write
        for i in reversed(range(len(extracts))):
            ok, off, length = extracts[i](rows, lengths)
            off_k, len_k = place(i, off, 0), place(i, length, -1)
            if member is None:
                # a row the extract does not take reads off 0, len -1
                member = jnp.where(ok, i32(i), i32(-1))
                off_u, len_u = off_k, len_k
            else:
                member = jnp.where(ok, i32(i), member)
                off_u = jnp.where(ok[:, None], off_k, off_u)
                len_u = jnp.where(ok[:, None], len_k, len_u)
        return member, off_u, len_u

    return match_list


class MatchListKernel:
    """Owns the jitted list program: ``(rows, lengths)`` -> ``(member,
    off, len)`` for recovery re-runs and placed dispatches, and the packed
    entry the streaming path takes (ops/packed_io.py).  A jit family of
    its own — ``jit_loong_grok_match_list`` on the profiler's ``XLA
    Modules`` line."""

    family = "grok_match_list"

    def __init__(self, programs: List[SegmentProgram], placement,
                 num_keys: int, pallas: bool, interpret: bool = False):
        from ..compile_watch import watched_jit
        from ..packed_io import packed_entry
        from .field_extract import build_extract_fn
        if pallas:
            from .field_extract_pallas import build_extract_fn_pallas
            extracts = [build_extract_fn_pallas(p, interpret=interpret)
                        for p in programs]
        else:
            extracts = [build_extract_fn(p) for p in programs]
        self.num_keys = num_keys
        match_list = build_match_list_fn(extracts, placement, num_keys)
        self._fn = watched_jit(match_list, self.family)
        self.packed_call, self.unpack = packed_entry(
            match_list,
            (("i32", None), ("i32", num_keys), ("i32", num_keys)),
            self.family)

    def __call__(self, rows, lengths):
        return self._fn(rows, lengths)

"""loongfuse: ahead-of-time multi-pattern DFA fusion (ROADMAP item 3).

Plain regex parses at ~1 GB/s host-native, but grok sits near 250 MB/s and
multiline collapsed on TPU — the per-pattern, per-stage execution model is
the bottleneck, not match speed.  This module compiles a pipeline's WHOLE
grok/regex/multiline pattern set ahead of time into one minimized
multi-accept DFA so a single scan classifies every pattern at once
(PAPERS.md: "Deterministic vs. Non Deterministic Finite Automata in
Automata Processing" for the dense-DFA layout; PaREM for the
parallel-split scan — here the split is the 4-wide interleaved row walk in
``lct_dfa_scan``).

Three layers:

1. **Compiler** (`compile_fused` / `load_or_compile`): per-pattern Thompson
   NFAs share one state space, a common ε-start forms the product, subset
   construction carries per-pattern accept TAGS, and Hopcroft minimization
   runs with the initial partition split by tag set.  Tiered caps: the
   fused automaton may use ``FUSED_MAX_STATES``/``FUSED_MAX_CLASSES``
   (host scan tables are byte-indexed, so only table bytes matter), while
   ``device_ok`` records whether it also fits the MXU kernel's dense
   [K·S, S] budget.  A pattern that blows the budget is DEMOTED — dropped
   from the automaton with a recorded reason and a one-shot alarm — and
   keeps running on its per-pattern path; fusion degrades, never breaks.
   Compiled automata are cached by pattern-set content hash under
   ``<data_dir>/dfa_cache/`` so restarts and hot-reloads skip compilation.

2. **Scanner** (`ByteTableScanner`): the runtime form is a byte-indexed
   transition table ``t256[s, b]`` (class compression applied at build
   time), walked by the native ``lct_dfa_scan`` 4 rows at a time, with a
   lockstep numpy fallback.  One pass returns a uint32 accept-tag bitmask
   per event.

3. **Execution** (`FusedSingleExec` / `FusedSetExec`): the accept tags GATE
   which Tier-1 extract program runs per event.  For a single trial-heavy
   pattern (grok composites), the pattern's residual choice points
   (optionals / alternations left after capture-interior relaxation) are
   enumerated into ≤``MAX_VARIANTS`` LINEAR variants in backtracking
   preference order; capture interiors whose language cannot contain the
   following delimiter byte are relaxed to plain class spans, so each
   variant compiles to the walker's fastest (mask-accelerated) form.  The
   optimistic path runs variant 0 first and validates only the relaxed
   interiors with small regional DFAs; rows that fail fall back to the
   authoritative fused scan, whose lowest set tag bit IS the backtracking
   preference.  For a pattern SET (grok Match lists, multiline
   start/continue/end), one scan replaces N per-pattern match passes.

Correctness contract: fused output is byte-identical to the per-pattern
path — enforced by the differential tests in tests/test_fuse.py, the grok
library goldens, and the scripts/fuse_equivalence.py lint gate.
"""

from __future__ import annotations

import ctypes
import hashlib
import itertools
import json
import os
import re
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from re import _constants as sre_c
from re import _parser as sre_parse

from ... import native as native_mod
from .charclass import CharClass
from .dfa import (DFAUnsupported, _NFA, build_pattern_nfa, compile_dfa,
                  strip_anchors)
from .native_exec import NativeT1Executor, try_build
from .program import compile_tier1

# ---------------------------------------------------------------------------
# Tiered caps.  Single-pattern Tier-2 stays at dfa.py's 64/32 (the legacy
# DFAMatchKernel budget).  The fused tiers:
#   * host scan tables are byte-indexed (classes folded at build time), so
#     the host cap is about table footprint: 2048 states × 256 × u16 = 1 MB.
#   * the device kernel keeps the dense [K·S, S] MXU mapping, so the fused
#     automaton is device-eligible only under the tighter caps below.
FUSED_MAX_STATES = 2048
FUSED_MAX_CLASSES = 96
DEVICE_MAX_STATES = 128
DEVICE_MAX_CLASSES = 48
MAX_PATTERNS = 32            # accept tags ride a uint32 bitmask
MAX_VARIANTS = 16
REGION_MAX_STATES = 512

CACHE_VERSION = 2            # bump when FusedDFA's serialized layout changes


class FuseUnsupported(Exception):
    pass


# ---------------------------------------------------------------------------
# Fused compile: product NFA -> multi-accept subset construction -> Hopcroft
# ---------------------------------------------------------------------------


@dataclass
class FusedDFA:
    patterns: List[str]           # fused members, priority order (bit i)
    names: List[str]
    num_states: int
    num_classes: int
    byte_class: np.ndarray        # [256] uint8
    transitions: np.ndarray       # [S, K] int32
    start: int
    accept_tags: np.ndarray       # [S] uint32 bitmask of accepting patterns
    demoted: List[Tuple[str, str, str]] = field(default_factory=list)
    stats: Dict[str, object] = field(default_factory=dict)

    @property
    def device_ok(self) -> bool:
        return (self.num_states <= DEVICE_MAX_STATES
                and self.num_classes <= DEVICE_MAX_CLASSES)

    def byte_class_intervals(self) -> List[List[Tuple[int, int]]]:
        out = []
        for k in range(self.num_classes):
            out.append(CharClass(self.byte_class == k).intervals())
        return out

    def match_cpu(self, data: bytes) -> int:
        """Reference interpreter (tests): accept-tag bitmask for `data`."""
        s = self.start
        for b in data:
            s = int(self.transitions[s, self.byte_class[b]])
        return int(self.accept_tags[s])


def _determinize(nfa: _NFA, starts: List[int], accepts: List[int],
                 max_states: int, max_classes: int
                 ) -> Tuple[np.ndarray, np.ndarray, int, np.ndarray]:
    """Multi-accept subset construction over a shared NFA.

    `starts[i]`/`accepts[i]` are pattern i's NFA entry/accept states; the
    DFA state containing accepts[i] carries tag bit i.  Returns
    (byte_class, transitions, start, accept_tags)."""
    n = len(nfa.eps)
    closure: List[frozenset] = []
    for i in range(n):
        seen = {i}
        stack = [i]
        while stack:
            s = stack.pop()
            for t in nfa.eps[s]:
                if t not in seen:
                    seen.add(t)
                    stack.append(t)
        closure.append(frozenset(seen))

    masks: List[np.ndarray] = []
    for s in range(n):
        for mask, _ in nfa.trans[s]:
            masks.append(mask)
    if masks:
        sig = np.stack(masks).astype(np.uint8)
        _, byte_class = np.unique(sig.T, axis=0, return_inverse=True)
        byte_class = byte_class.astype(np.uint8)
    else:
        byte_class = np.zeros(256, dtype=np.uint8)
    num_classes = int(byte_class.max()) + 1
    if num_classes > max_classes:
        raise DFAUnsupported(f"{num_classes} byte classes > {max_classes}")
    class_rep = np.zeros(num_classes, dtype=np.int32)
    for k in range(num_classes):
        class_rep[k] = int(np.argmax(byte_class == k))

    def step(states: frozenset, byte: int) -> frozenset:
        out: set = set()
        for s in states:
            for mask, t in nfa.trans[s]:
                if mask[byte]:
                    out.update(closure[t])
        return frozenset(out)

    start_set = frozenset().union(*(closure[s] for s in starts)) \
        if starts else frozenset()
    dfa_states: Dict[frozenset, int] = {}
    order: List[frozenset] = []

    def intern(fs: frozenset) -> int:
        if fs not in dfa_states:
            if len(order) >= max_states:
                raise DFAUnsupported(f"fused DFA exceeds {max_states} states")
            dfa_states[fs] = len(order)
            order.append(fs)
        return dfa_states[fs]

    dead_id = intern(frozenset())
    start_id = intern(start_set)
    trans_rows: List[List[int]] = [[dead_id] * num_classes]
    i = 1
    while i < len(order):
        fs = order[i]
        trans_rows.append(
            [intern(step(fs, int(class_rep[k]))) for k in range(num_classes)])
        i += 1

    transitions = np.array(trans_rows, dtype=np.int32)
    accept_tags = np.zeros(len(order), dtype=np.uint32)
    for bit, acc in enumerate(accepts):
        for sid, fs in enumerate(order):
            if acc in fs:
                accept_tags[sid] |= np.uint32(1 << bit)
    return byte_class, transitions, start_id, accept_tags


def _hopcroft(transitions: np.ndarray, accept_tags: np.ndarray,
              start: int) -> Tuple[np.ndarray, np.ndarray, int]:
    """Partition-refinement minimization preserving accept TAG SETS (two
    states are distinguishable when their tag bitmasks differ — required
    for multi-accept: merging tag-1 and tag-2 acceptors would conflate
    patterns)."""
    S, K = transitions.shape
    # initial partition: states grouped by tag value
    block_of = np.zeros(S, dtype=np.int64)
    blocks: Dict[int, int] = {}
    for s in range(S):
        t = int(accept_tags[s])
        if t not in blocks:
            blocks[t] = len(blocks)
        block_of[s] = blocks[t]
    n_blocks = len(blocks)

    # inverse transition lists: inv[k][s'] = states s with δ(s,k)=s'
    inv: List[List[List[int]]] = [[[] for _ in range(S)] for _ in range(K)]
    for s in range(S):
        for k in range(K):
            inv[k][int(transitions[s, k])].append(s)

    members: List[set] = [set() for _ in range(n_blocks)]
    for s in range(S):
        members[block_of[s]].add(s)
    worklist = set(range(n_blocks))
    while worklist:
        a = worklist.pop()
        splitter = list(members[a])
        for k in range(K):
            x = set()
            for sprime in splitter:
                x.update(inv[k][sprime])
            if not x:
                continue
            # split every block that x cuts
            touched: Dict[int, set] = {}
            for s in x:
                touched.setdefault(block_of[s], set()).add(s)
            for b, inter in touched.items():
                if len(inter) == len(members[b]):
                    continue
                new_b = len(members)
                members.append(inter)
                members[b] -= inter
                for s in inter:
                    block_of[s] = new_b
                if b in worklist:
                    worklist.add(new_b)
                else:
                    worklist.add(
                        new_b if len(inter) <= len(members[b]) else b)

    # renumber blocks reachability-first so ids are dense and stable
    n_final = len(members)
    new_trans = np.zeros((n_final, K), dtype=np.int32)
    new_tags = np.zeros(n_final, dtype=np.uint32)
    rep = [min(m) if m else 0 for m in members]
    for b in range(n_final):
        r = rep[b]
        new_tags[b] = accept_tags[r]
        for k in range(K):
            new_trans[b, k] = block_of[int(transitions[r, k])]
    return new_trans, new_tags, int(block_of[start])


def compile_fused(patterns: Sequence[str],
                  names: Optional[Sequence[str]] = None,
                  max_states: int = FUSED_MAX_STATES,
                  max_classes: int = FUSED_MAX_CLASSES,
                  alarm_demotions: bool = True,
                  note_demotions: bool = True) -> FusedDFA:
    """AOT-fuse `patterns` (priority order) into one multi-accept DFA.

    Patterns that cannot join (unsupported constructs, or the set blows the
    tiered state/class budget) are demoted with a recorded reason; the
    remaining set still fuses.  Raises FuseUnsupported only when NO pattern
    survives."""
    t0 = time.perf_counter()
    names = list(names) if names is not None else \
        [f"p{i}" for i in range(len(patterns))]
    patterns = [p.decode("latin-1") if isinstance(p, bytes) else p
                for p in patterns]
    demoted: List[Tuple[str, str, str]] = []

    # individually validate + size each pattern (the demotion heuristic
    # needs per-pattern state counts to pick the budget-blowing culprit)
    sizes: Dict[int, int] = {}
    kept: List[int] = []
    for i, p in enumerate(patterns):
        try:
            nfa_i = _NFA()
            _, s_i, a_i = build_pattern_nfa(p, nfa_i)
            bc_i, tr_i, _, _ = _determinize(
                nfa_i, [s_i], [a_i], max_states, max_classes)
            sizes[i] = tr_i.shape[0]
            kept.append(i)
        except DFAUnsupported as e:
            demoted.append((names[i], p, f"unsupported: {e}"))
    while len(kept) > MAX_PATTERNS:
        i = kept.pop()
        demoted.append((names[i], patterns[i],
                        f"pattern set exceeds {MAX_PATTERNS} accept tags"))

    byte_class = transitions = accept_tags = None
    start = 0
    while kept:
        nfa = _NFA()
        starts, accepts = [], []
        try:
            for i in kept:
                _, s_i, a_i = build_pattern_nfa(patterns[i], nfa)
                starts.append(s_i)
                accepts.append(a_i)
            byte_class, transitions, start, accept_tags = _determinize(
                nfa, starts, accepts, max_states, max_classes)
            transitions, accept_tags, start = _hopcroft(
                transitions, accept_tags, start)
            break
        except DFAUnsupported as e:
            # demote the largest individual contributor and retry
            worst = max(kept, key=lambda i: sizes[i])
            kept.remove(worst)
            demoted.append((names[worst], patterns[worst],
                            f"fused budget: {e}"))
    if not kept:
        if note_demotions:
            for nm, p, reason in demoted:
                note_demotion(p, reason, alarm=alarm_demotions)
        raise FuseUnsupported("no pattern in the set is fusable")

    compile_ms = (time.perf_counter() - t0) * 1e3
    fdfa = FusedDFA(
        patterns=[patterns[i] for i in kept],
        names=[names[i] for i in kept],
        num_states=transitions.shape[0],
        num_classes=transitions.shape[1],
        byte_class=byte_class,
        transitions=transitions,
        start=start,
        accept_tags=accept_tags,
        demoted=demoted,
        stats={"compile_ms": round(compile_ms, 2),
               "states": int(transitions.shape[0]),
               "classes": int(transitions.shape[1]),
               "n_patterns": len(kept),
               "n_demoted": len(demoted),
               "cache": "miss"},
    )
    if note_demotions:
        for nm, p, reason in demoted:
            note_demotion(p, reason, alarm=alarm_demotions)
    _note_compile(fdfa)
    return fdfa

# ---------------------------------------------------------------------------
# Runtime scanner: byte-indexed tables + native 4-wide interleaved walk
# ---------------------------------------------------------------------------


def _bind_scan(lib) -> bool:
    if getattr(lib, "_dfa_scan_bound", False):
        return True
    if not hasattr(lib, "lct_dfa_scan"):
        return False
    p = ctypes.c_void_p
    lib.lct_dfa_scan.restype = ctypes.c_int64
    lib.lct_dfa_scan.argtypes = [
        p, ctypes.c_int64, p, p, ctypes.c_int64,
        p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32, p, p]
    lib._dfa_scan_bound = True
    return True


class ByteTableScanner:
    """One fused automaton in runtime form: ``t256[s, b]`` with the class
    compression folded in at build time, so the scan's serial dependency is
    a single L1-resident load per byte.  u8 state ids when S ≤ 256 (the
    whole table stays L1-resident for typical fused sets), u16 above."""

    def __init__(self, byte_class: np.ndarray, transitions: np.ndarray,
                 start: int, accept_tags: np.ndarray):
        S = transitions.shape[0]
        t256 = transitions[:, byte_class]            # [S, 256]
        self.wide = S > 256
        dtype = np.uint16 if self.wide else np.uint8
        self.t256 = np.ascontiguousarray(t256.astype(dtype))
        self.start = int(start)
        self.accept_tags = np.ascontiguousarray(
            accept_tags.astype(np.uint32))
        self.num_states = S

    @classmethod
    def from_fused(cls, fdfa: FusedDFA) -> "ByteTableScanner":
        return cls(fdfa.byte_class, fdfa.transitions, fdfa.start,
                   fdfa.accept_tags)

    @classmethod
    def from_dfa(cls, dfa) -> "ByteTableScanner":
        """Single-pattern Tier-2 DFA (dfa.py) as a host scanner: bit 0 set
        ⇔ match.  Replaces the per-row Python `re` loop that made the
        DFA tier's host path two orders of magnitude slower than this."""
        tags = np.where(dfa.accepting, 1, 0).astype(np.uint32)
        return cls(dfa.byte_class, dfa.transitions, dfa.start, tags)

    def scan(self, arena: np.ndarray, offsets: np.ndarray,
             lengths: np.ndarray) -> np.ndarray:
        """uint32 accept-tag bitmask per row.  Negative lengths (absent
        spans) scan as empty strings."""
        offsets = np.ascontiguousarray(offsets, dtype=np.int64)
        lengths = np.ascontiguousarray(lengths, dtype=np.int32)
        n = len(offsets)
        out = np.zeros(n, dtype=np.uint32)
        if n == 0:
            return out
        arena = np.ascontiguousarray(arena, dtype=np.uint8)
        lib = native_mod.get_lib()
        if lib is not None and _bind_scan(lib):
            rc = lib.lct_dfa_scan(
                arena.ctypes.data, len(arena),
                offsets.ctypes.data, lengths.ctypes.data, n,
                self.t256.ctypes.data, self.num_states,
                1 if self.wide else 0, self.start,
                self.accept_tags.ctypes.data, out.ctypes.data)
            if rc == 0:
                return out
        return self._scan_numpy(arena, offsets, lengths, out)

    def _scan_numpy(self, arena, offsets, lengths, out) -> np.ndarray:
        """Lockstep fallback when the native library is absent: all rows
        advance one byte column per step (the same schedule as the device
        kernel, gather-based)."""
        lens = np.maximum(lengths, 0)
        # native contract: a span outside the arena scans to tag 0 — never
        # a partial-prefix state (the two fallbacks must agree)
        oob = (offsets < 0) | (offsets + lens > len(arena))
        lens = np.where(oob, 0, lens)
        states = np.full(len(offsets), self.start, dtype=np.int64)
        max_len = int(lens.max()) if len(lens) else 0
        alive = np.nonzero(lens > 0)[0]
        for p in range(max_len):
            alive = alive[lens[alive] > p]
            if not len(alive):
                break
            b = arena[offsets[alive] + p]
            states[alive] = self.t256[states[alive], b]
        out[:] = self.accept_tags[states]
        out[oob] = 0
        return out


# ---------------------------------------------------------------------------
# Compile cache: pattern-set content hash -> persisted automaton
# ---------------------------------------------------------------------------

_cache_dir: Optional[str] = None
# LRU-bounded like engine._engine_cache: pattern-set churn across pipeline
# hot-reloads must not pin every compiled automaton (~up to 1 MB of tables
# each) for the process lifetime
_mem_cache: "OrderedDict[str, FusedDFA]" = OrderedDict()
_mem_cache_lock = threading.Lock()
_MEM_CACHE_MAX = 128


def set_cache_dir(path: Optional[str]) -> None:
    """Application startup hook (mirrors flight.set_dump_dir): fused
    automata persist under ``<data_dir>/dfa_cache/``."""
    global _cache_dir
    _cache_dir = path


def _resolved_cache_dir() -> Optional[str]:
    env = os.environ.get("LOONG_DFA_CACHE")
    if env:
        return env
    return _cache_dir


def _set_key(patterns: Sequence[str], max_states: int,
             max_classes: int) -> str:
    blob = json.dumps([CACHE_VERSION, max_states, max_classes,
                       list(patterns)], ensure_ascii=False)
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:20]


def _cache_path(dirname: str, key: str) -> str:
    return os.path.join(dirname, "dfa_cache", f"v{CACHE_VERSION}_{key}.npz")


def _save_cache(path: str, fdfa: FusedDFA) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    meta = json.dumps({
        "version": CACHE_VERSION,
        "patterns": fdfa.patterns,
        "names": fdfa.names,
        "demoted": fdfa.demoted,
        "stats": {k: v for k, v in fdfa.stats.items() if k != "cache"},
    })
    tmp = path + f".tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            np.savez(f,
                     byte_class=fdfa.byte_class,
                     transitions=fdfa.transitions,
                     start=np.int64(fdfa.start),
                     accept_tags=fdfa.accept_tags,
                     meta=np.frombuffer(meta.encode("utf-8"), np.uint8))
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _load_cache(path: str, patterns: Sequence[str]) -> Optional[FusedDFA]:
    try:
        with np.load(path, allow_pickle=False) as z:
            meta = json.loads(bytes(z["meta"].tobytes()).decode("utf-8"))
            if meta.get("version") != CACHE_VERSION:
                return None
            byte_class = z["byte_class"]
            transitions = z["transitions"]
            start = int(z["start"])
            accept_tags = z["accept_tags"]
    except (OSError, KeyError, ValueError, json.JSONDecodeError):
        return None
    # hash collision / stale-content guard: the SET as given must resolve
    # to exactly the stored fused-member + demotion split
    stored_all = list(meta["patterns"]) + [p for _, p, _ in meta["demoted"]]
    if sorted(stored_all) != sorted(patterns):
        return None
    stats = dict(meta.get("stats", {}))
    stats["cache"] = "hit"
    return FusedDFA(
        patterns=list(meta["patterns"]),
        names=list(meta["names"]),
        num_states=transitions.shape[0],
        num_classes=transitions.shape[1],
        byte_class=byte_class,
        transitions=transitions,
        start=start,
        accept_tags=accept_tags,
        demoted=[tuple(d) for d in meta["demoted"]],
        stats=stats,
    )


def load_or_compile(patterns: Sequence[str],
                    names: Optional[Sequence[str]] = None,
                    max_states: int = FUSED_MAX_STATES,
                    max_classes: int = FUSED_MAX_CLASSES,
                    note_demotions: bool = True) -> FusedDFA:
    """`compile_fused` behind the two-level cache: in-process (pipeline
    reloads reuse the object) and on-disk (restarts skip compilation)."""
    patterns = [p.decode("latin-1") if isinstance(p, bytes) else p
                for p in patterns]
    key = _set_key(patterns, max_states, max_classes)
    with _mem_cache_lock:
        got = _mem_cache.get(key)
        if got is not None:
            _mem_cache.move_to_end(key)          # LRU touch
    if got is not None:
        _count("fuse_cache_hit_total")
        return got
    dirname = _resolved_cache_dir()
    if dirname:
        fdfa = _load_cache(_cache_path(dirname, key), patterns)
        if fdfa is not None:
            _count("fuse_cache_hit_total")
            # replay demotions: the cache carries the demoted split, but the
            # counter/alarm are process-level — without this a restart makes
            # the off-device fallback silent again
            if note_demotions:
                for _nm, p, reason in fdfa.demoted:
                    note_demotion(p, reason)
            _note_compile(fdfa, cached=True)
            _memoize(key, fdfa)
            return fdfa
    _count("fuse_cache_miss_total")
    fdfa = compile_fused(patterns, names=names, max_states=max_states,
                         max_classes=max_classes,
                         note_demotions=note_demotions)
    if dirname:
        _save_cache(_cache_path(dirname, key), fdfa)
    _memoize(key, fdfa)
    return fdfa


def _fdfa_nbytes(fdfa: FusedDFA) -> int:
    """Device-constant footprint of one memoized automaton (the tables a
    dispatch keeps resident): transition matrix + byte classes + accept
    tags — the ``dfa_tables`` device-memory family's unit."""
    total = 0
    for name in ("transitions", "byte_class", "accept_tags"):
        arr = getattr(fdfa, name, None)
        total += getattr(arr, "nbytes", 0) or 0
    return total


def _memoize(key: str, fdfa: FusedDFA) -> None:
    from ..device_plane import mem_note_alloc, mem_note_free
    evicted: List[FusedDFA] = []
    with _mem_cache_lock:
        fresh = key not in _mem_cache
        _mem_cache[key] = fdfa
        _mem_cache.move_to_end(key)
        while len(_mem_cache) > _MEM_CACHE_MAX:
            evicted.append(
                _mem_cache.popitem(last=False)[1])   # evict LRU
    # dfa_tables ledger (loongxprof): tables live while memoized, credit
    # back on eviction — outside the cache lock
    if fresh:
        mem_note_alloc("dfa_tables", _fdfa_nbytes(fdfa))
    for old in evicted:
        mem_note_free("dfa_tables", _fdfa_nbytes(old))


# ---------------------------------------------------------------------------
# Observability: compile stats, demotion counter + one-shot alarm
# ---------------------------------------------------------------------------

_stats_lock = threading.Lock()
_metrics_rec = None
_alarmed: set = set()
_fusion_state: Dict[str, object] = {
    "compiles": 0, "cache_hits": 0, "cache_misses": 0, "demotions": 0,
    "sets": [],                 # last 8 compiled/loaded sets
}


def _metrics():
    global _metrics_rec
    if _metrics_rec is None:
        with _stats_lock:
            if _metrics_rec is None:
                from ...monitor.metrics import MetricsRecord
                _metrics_rec = MetricsRecord(
                    category="component", labels={"component": "loongfuse"})
    return _metrics_rec


def _count(name: str, delta: int = 1) -> None:
    try:
        _metrics().counter(name).add(delta)
    except Exception:  # noqa: BLE001 — stats must never break parsing
        pass
    with _stats_lock:
        if name == "fuse_cache_hit_total":
            _fusion_state["cache_hits"] += delta
        elif name == "fuse_cache_miss_total":
            _fusion_state["cache_misses"] += delta
        elif name == "regex_tier_demotions":
            _fusion_state["demotions"] += delta


def _note_compile(fdfa: FusedDFA, cached: bool = False) -> None:
    try:
        rec = _metrics()
        if not cached:
            rec.counter("fuse_compile_total").add(1)
            rec.counter("fuse_compile_ms_total").add(
                int(fdfa.stats.get("compile_ms", 0)))
        rec.gauge("fused_dfa_states").set(fdfa.num_states)
        rec.gauge("fused_dfa_classes").set(fdfa.num_classes)
    except Exception:  # noqa: BLE001
        pass
    entry = {"names": list(fdfa.names), "states": fdfa.num_states,
             "classes": fdfa.num_classes,
             "device_ok": fdfa.device_ok,
             "demoted": [(nm, reason) for nm, _, reason in fdfa.demoted],
             **{k: v for k, v in fdfa.stats.items()}}
    with _stats_lock:
        if not cached:
            _fusion_state["compiles"] += 1
        sets = _fusion_state["sets"]
        sets.append(entry)
        del sets[:-8]


def note_demotion(pattern: str, reason: str, pipeline: str = "",
                  alarm: bool = True) -> None:
    """A pattern fell off the device tier (fused budget, DFA caps,
    capture-needing Tier-2).  Counted always; alarmed ONCE per pattern —
    the silent-fallback failure mode this exists to kill is a TPU
    throughput collapse (multiline-java's 1.6 MB/s) that nothing reported."""
    _count("regex_tier_demotions")
    if not alarm:
        return
    with _stats_lock:
        if pattern in _alarmed:
            return
        _alarmed.add(pattern)
    try:
        from ...monitor.alarms import AlarmManager, AlarmType
        AlarmManager.instance().send_alarm(
            AlarmType.REGEX_TIER_DEMOTED,
            f"regex demoted off device tier ({reason}): {pattern[:160]}",
            pipeline=pipeline)
    except Exception:  # noqa: BLE001
        pass


def fusion_status() -> Dict[str, object]:
    """The /debug/status `fusion` section."""
    with _stats_lock:
        return {
            "compiles": _fusion_state["compiles"],
            "cache_hits": _fusion_state["cache_hits"],
            "cache_misses": _fusion_state["cache_misses"],
            "demotions": _fusion_state["demotions"],
            "sets": [dict(s) for s in _fusion_state["sets"]],
        }


def reset_for_testing() -> None:
    """Clear process-level fusion state (mem cache, one-shot alarms,
    status counters).  Metrics records persist — they are process-lifetime
    instruments like shared_histogram's."""
    global _cache_dir
    from ..device_plane import mem_note_free
    with _mem_cache_lock:
        dropped = list(_mem_cache.values())
        _mem_cache.clear()
    for fdfa in dropped:
        mem_note_free("dfa_tables", _fdfa_nbytes(fdfa))
    with _stats_lock:
        _alarmed.clear()
        _fusion_state.update(compiles=0, cache_hits=0, cache_misses=0,
                             demotions=0, sets=[])
    _cache_dir = None

# ---------------------------------------------------------------------------
# Single-pattern variant linearization
#
# A grok composite compiles to a Tier-1 program full of Optional_/Alt trial
# ops — the walker re-tries them per row, which is the measured 4× gap vs a
# linear program.  The fused DFA carries FULL original semantics, so
# extraction can be gated: enumerate the pattern's residual choice points
# into linear variants (preference order = re's backtracking order), relax
# capture interiors that end at a delimiter byte their language excludes,
# and let the accept tag pick the variant per event.
# ---------------------------------------------------------------------------

_END = -1          # follow sentinel: end of pattern (a forced boundary)

MAXREPEAT = sre_c.MAXREPEAT


@dataclass(eq=False)
class _FLit:
    data: bytes


@dataclass(eq=False)
class _FCls:
    mask: np.ndarray              # bool [256]
    lo: int
    hi: Optional[int]             # None = unbounded
    lazy: bool = False


@dataclass(eq=False)
class _FSeq:
    items: list


@dataclass(eq=False)
class _FAlt:
    branches: List["_FSeq"]


@dataclass(eq=False)
class _FOpt:
    body: "_FSeq"
    lazy: bool = False


@dataclass(eq=False)
class _FGrp:
    cap: Optional[int]            # 1-based group number, None = (?:)
    body: "_FSeq"


@dataclass(eq=False)
class _FRng:
    """Composite-body repeat (?:X){lo,hi}.  hi=None is unbounded.  Bounded
    small ranges are EXPANDED into nested optionals before choice
    enumeration (X{1,2} → X(?:X)? — greedy prefers the longer count, same
    as re); anything left un-expanded can only survive inside a relaxed
    region, where the fused DFA owns its exact semantics."""
    body: "_FSeq"
    lo: int
    hi: Optional[int]
    lazy: bool = False


@dataclass(eq=False)
class _FRlx:
    cap: int                      # 1-based group number
    mask: np.ndarray              # interior alphabet (span class)
    region: "_FSeq"               # ORIGINAL body (exact grammar)


def _tok_to_ast(tokens) -> _FSeq:
    items: list = []
    for op, av in tokens:
        if op is sre_c.LITERAL:
            items.append(_FLit(bytes([av])))
        elif op is sre_c.NOT_LITERAL:
            items.append(_FCls(CharClass.single(av).negated().mask, 1, 1))
        elif op is sre_c.IN:
            items.append(_FCls(CharClass.from_sre_in(av).mask, 1, 1))
        elif op is sre_c.ANY:
            items.append(_FCls(CharClass.dot().mask, 1, 1))
        elif op is sre_c.CATEGORY:
            items.append(_FCls(CharClass.from_category(av).mask, 1, 1))
        elif op is sre_c.SUBPATTERN:
            g, add_flags, del_flags, sub = av
            if add_flags or del_flags:
                raise FuseUnsupported("inline flags")
            items.append(_FGrp(g, _tok_to_ast(list(sub))))
        elif op is sre_c.BRANCH:
            _, alts = av
            items.append(_FAlt([_tok_to_ast(list(a)) for a in alts]))
        elif op in (sre_c.MAX_REPEAT, sre_c.MIN_REPEAT):
            lo, hi, sub = av
            lazy = op is sre_c.MIN_REPEAT
            body = _tok_to_ast(list(sub))
            if len(body.items) == 1 and isinstance(body.items[0], _FCls) \
                    and body.items[0].lo == 1 and body.items[0].hi == 1:
                items.append(_FCls(body.items[0].mask, lo,
                                   None if hi is MAXREPEAT else int(hi),
                                   lazy))
            elif (lo, hi) == (0, 1):
                items.append(_FOpt(body, lazy))
            else:
                items.append(_FRng(body, lo,
                                   None if hi is MAXREPEAT else int(hi),
                                   lazy))
        else:
            raise FuseUnsupported(f"op {op}")
    return _FSeq(items)


def _alphabet(node) -> np.ndarray:
    m = np.zeros(256, dtype=bool)
    if isinstance(node, _FLit):
        for b in node.data:
            m[b] = True
    elif isinstance(node, _FCls):
        m |= node.mask
    elif isinstance(node, _FSeq):
        for it in node.items:
            m |= _alphabet(it)
    elif isinstance(node, _FAlt):
        for br in node.branches:
            m |= _alphabet(br)
    elif isinstance(node, (_FOpt, _FGrp, _FRng)):
        m |= _alphabet(node.body)
    elif isinstance(node, _FRlx):
        m |= node.mask
    return m


def _has_group(node) -> bool:
    if isinstance(node, _FGrp):
        return True
    if isinstance(node, _FSeq):
        return any(_has_group(i) for i in node.items)
    if isinstance(node, _FAlt):
        return any(_has_group(b) for b in node.branches)
    if isinstance(node, (_FOpt, _FRng)):
        return _has_group(node.body)
    return False


def _has_trials(node) -> bool:
    """Does the subtree contain COMPOSITE trial ops (optionals /
    alternations / composite repeats)?  Only such capture interiors are
    worth relaxing: a pure class-quantifier run (`[+-]?\\d+`) already
    compiles to trial-free Span ops, so relaxing it would spend a regional
    validation for nothing."""
    if isinstance(node, (_FAlt, _FOpt, _FRng)):
        return True
    if isinstance(node, _FSeq):
        return any(_has_trials(i) for i in node.items)
    if isinstance(node, _FGrp):
        return _has_trials(node.body)
    return False


def _min_len(node) -> int:
    """Minimum match length of a subtree (saturating small int)."""
    if isinstance(node, _FLit):
        return len(node.data)
    if isinstance(node, _FCls):
        return node.lo
    if isinstance(node, _FSeq):
        return sum(_min_len(i) for i in node.items)
    if isinstance(node, _FAlt):
        return min((_min_len(b) for b in node.branches), default=0)
    if isinstance(node, _FOpt):
        return 0
    if isinstance(node, _FGrp):
        return _min_len(node.body)
    if isinstance(node, _FRng):
        return node.lo * _min_len(node.body)
    if isinstance(node, _FRlx):
        return 0
    return 0


# Regions shorter than this stay EXACT in the walker: validating a 3-byte
# span with a separate DFA pass costs more than the walker's own trial,
# and pinned variants absorb the residual choice points anyway.
_MIN_RELAX_LEN = 4


def _clone(node):
    """Fresh node objects for repeat expansion — choice points are keyed
    by identity, so each expanded copy must decide independently."""
    if isinstance(node, _FSeq):
        return _FSeq([_clone(i) for i in node.items])
    if isinstance(node, _FLit):
        return _FLit(node.data)
    if isinstance(node, _FCls):
        return _FCls(node.mask, node.lo, node.hi, node.lazy)
    if isinstance(node, _FAlt):
        return _FAlt([_clone(b) for b in node.branches])
    if isinstance(node, _FOpt):
        return _FOpt(_clone(node.body), node.lazy)
    if isinstance(node, _FGrp):
        return _FGrp(node.cap, _clone(node.body))
    if isinstance(node, _FRng):
        return _FRng(_clone(node.body), node.lo, node.hi, node.lazy)
    if isinstance(node, _FRlx):
        return _FRlx(node.cap, node.mask, node.region)
    raise FuseUnsupported(f"clone {type(node).__name__}")


_MAX_RNG_EXPAND = 4


def _expand_rngs(node):
    """Rewrite small bounded composite repeats into mandatory copies plus
    a nested optional chain, in re's preference order: greedy X{1,2} →
    X(?:X)? (longer count first), lazy X{1,2}? → X(?:X)?? (shorter
    first).  Relaxed regions keep their original form — the fused DFA owns
    them."""
    if isinstance(node, _FSeq):
        return _FSeq([_expand_rngs(i) for i in node.items])
    if isinstance(node, _FAlt):
        return _FAlt([_expand_rngs(b) for b in node.branches])
    if isinstance(node, _FOpt):
        return _FOpt(_expand_rngs(node.body), node.lazy)
    if isinstance(node, _FGrp):
        return _FGrp(node.cap, _expand_rngs(node.body))
    if isinstance(node, _FRng):
        body = _expand_rngs(node.body)
        if node.hi is None or node.hi - node.lo > _MAX_RNG_EXPAND \
                or _has_group(body):
            return _FRng(body, node.lo, node.hi, node.lazy)
        items = [_clone(body) for _ in range(node.lo)]
        tail = None
        for _ in range(node.hi - node.lo):
            inner = _FSeq([_clone(body)] + ([tail] if tail else []))
            tail = _FOpt(inner, node.lazy)
        if tail is not None:
            items.append(tail)
        return _FSeq(items)
    return node


def _relax_seq(seq: _FSeq, follow) -> _FSeq:
    """Rewrite capture groups to relaxed class spans where sound.

    A group G directly followed by a literal whose first byte d is OUTSIDE
    G's interior alphabet A (or sitting at the very end of the pattern) has
    a FORCED boundary: in any accepted string G's span is exactly the
    maximal A-run, so `[A]*` reproduces re's spans on validated rows.  The
    exact interior grammar moves to the regional validator / fused DFA."""
    out: list = []
    n = len(seq.items)
    for i, it in enumerate(seq.items):
        if i + 1 < n:
            nxt = seq.items[i + 1]
            item_follow = nxt.data[0] if isinstance(nxt, _FLit) else None
        else:
            item_follow = follow
        if isinstance(it, _FGrp) and it.cap is not None:
            alpha = _alphabet(it.body)
            boundary_ok = (item_follow is _END
                           or (item_follow is not None
                               and not alpha[item_follow]))
            if boundary_ok and _has_trials(it.body) \
                    and not _has_group(it.body) \
                    and _min_len(it.body) >= _MIN_RELAX_LEN:
                out.append(_FRlx(it.cap, alpha, it.body))
                continue
            out.append(_FGrp(it.cap, _relax_seq(it.body, item_follow)))
        elif isinstance(it, _FGrp):
            out.append(_FGrp(None, _relax_seq(it.body, item_follow)))
        elif isinstance(it, _FOpt):
            # when the optional is taken, its tail sees the optional's own
            # follow (the delimiter appears either way)
            out.append(_FOpt(_relax_seq(it.body, item_follow), it.lazy))
        elif isinstance(it, _FAlt):
            out.append(_FAlt([_relax_seq(b, item_follow)
                              for b in it.branches]))
        else:
            out.append(it)
    return _FSeq(out)


def _collect_choices(node, out: list, in_rep: list) -> None:
    """DFS choice points in syntactic order — which for a concatenative
    pattern is exactly re's backtracking decision order, so enumerating
    assignments lexicographically yields variants in preference order."""
    if isinstance(node, _FSeq):
        for it in node.items:
            _collect_choices(it, out, in_rep)
    elif isinstance(node, _FOpt):
        out.append((node, 2))
        _collect_choices(node.body, out, in_rep)
    elif isinstance(node, _FAlt):
        out.append((node, len(node.branches)))
        for b in node.branches:
            _collect_choices(b, out, in_rep)
    elif isinstance(node, _FGrp):
        _collect_choices(node.body, out, in_rep)
    elif isinstance(node, _FRng):
        if node.hi is not None and node.hi != node.lo:
            in_rep.append(node)      # un-expanded bounded range: bail
        probe: list = []
        _collect_choices(node.body, probe, in_rep)
        if probe:
            # per-iteration choices cannot be pinned set-wide
            in_rep.append(node)


def _pin(node, decisions: Dict[int, int]):
    """Resolve choice points per `decisions` (keyed by node id).  Un-taken
    subtrees vanish — their capture groups stay unmatched (span -1), the
    same as re."""
    if isinstance(node, _FSeq):
        out = []
        for it in node.items:
            p = _pin(it, decisions)
            if p is not None:
                out.append(p)
        return _FSeq(out)
    if isinstance(node, _FOpt):
        choice = decisions[id(node)]
        present = (choice == 0) if not node.lazy else (choice == 1)
        return _pin(node.body, decisions) if present else None
    if isinstance(node, _FAlt):
        return _pin(node.branches[decisions[id(node)]], decisions)
    if isinstance(node, _FGrp):
        return _FGrp(node.cap, _pin(node.body, decisions))
    if isinstance(node, _FRng):
        return _FRng(_pin(node.body, decisions), node.lo, node.hi,
                     node.lazy)
    return node


_CLS_ESCAPE = {ord("\\"), ord("]"), ord("^"), ord("-")}


def _class_str(mask: np.ndarray) -> str:
    if mask.all():
        return r"[\x00-\xff]"
    parts = []
    for lo, hi in CharClass(mask).intervals():
        def esc(b):
            if b in _CLS_ESCAPE or b < 0x21 or b > 0x7e:
                return f"\\x{b:02x}"
            return chr(b)
        parts.append(esc(lo) if lo == hi else f"{esc(lo)}-{esc(hi)}")
    return "[" + "".join(parts) + "]"


def _quant(lo: int, hi: Optional[int], lazy: bool) -> str:
    if (lo, hi) == (1, 1):
        return ""
    if hi is None:
        q = "*" if lo == 0 else ("+" if lo == 1 else f"{{{lo},}}")
    elif lo == hi:
        q = f"{{{lo}}}"
    else:
        q = f"{{{lo},{hi}}}"
    return q + ("?" if lazy and q else "")


def _emit(node, caps_out: Optional[list], relaxed_as_class: bool) -> str:
    """Pinned AST -> regex string.  caps_out collects surviving capture
    group numbers in emission order (the walker's cap index mapping);
    None emits everything non-capturing (the fused DFA's exact variants)."""
    if isinstance(node, _FSeq):
        return "".join(_emit(i, caps_out, relaxed_as_class)
                       for i in node.items)
    if isinstance(node, _FLit):
        return re.escape(node.data.decode("latin-1"))
    if isinstance(node, _FCls):
        return _class_str(node.mask) + _quant(node.lo, node.hi, node.lazy)
    if isinstance(node, _FGrp):
        body = _emit(node.body, caps_out, relaxed_as_class)
        if node.cap is not None and caps_out is not None:
            caps_out.append(node.cap)
            return f"({body})"
        return f"(?:{body})"
    if isinstance(node, _FRlx):
        if relaxed_as_class:
            body = _class_str(node.mask) + "*"
        else:
            body = _emit(node.region, None, False)
        if caps_out is not None:
            caps_out.append(node.cap)
            return f"({body})"
        return f"(?:{body})"
    if isinstance(node, _FRng):
        return ("(?:" + _emit(node.body, caps_out, relaxed_as_class)
                + ")" + _quant(node.lo, node.hi, node.lazy))
    if isinstance(node, _FOpt):
        q = "??" if node.lazy else "?"
        return ("(?:" + _emit(node.body, caps_out, relaxed_as_class)
                + ")" + q)
    if isinstance(node, _FAlt):
        return ("(?:" + "|".join(_emit(b, caps_out, relaxed_as_class)
                                 for b in node.branches) + ")")
    raise FuseUnsupported(f"emit {type(node).__name__}")


def _walk_rlx(node, out: list) -> None:
    # every container _relax_seq recurses into must be walked here, or a
    # relaxed interior ships without its regional validator (an un-taken
    # optional/branch region simply has span -1 at parse time)
    if isinstance(node, _FSeq):
        for it in node.items:
            _walk_rlx(it, out)
    elif isinstance(node, _FRlx):
        out.append(node)
    elif isinstance(node, (_FGrp, _FRng, _FOpt)):
        _walk_rlx(node.body, out)
    elif isinstance(node, _FAlt):
        for b in node.branches:
            _walk_rlx(b, out)

# ---------------------------------------------------------------------------
# Execution: fused single-pattern extract + fused pattern-set classify
# ---------------------------------------------------------------------------


@dataclass(eq=False)
class _Variant:
    pattern: str                  # relaxed+pinned walker form
    exact: str                    # pinned exact form (fused DFA member)
    exec: NativeT1Executor
    cap_map: List[int]            # walker cap g -> ORIGINAL cap index (0-based)


class FusedSingleExec:
    """Host-tier fused execution of ONE trial-heavy pattern.

    Optimistic pipeline: variant 0 (re's most-preferred choice assignment)
    runs as a LINEAR native walk over all rows; relaxed capture interiors
    are then validated by small regional DFAs over exactly the captured
    spans (a few % of the bytes).  Rows that fail either step take the
    authoritative fused scan, whose lowest set accept bit is the
    backtracking-preferred variant, and re-extract on that variant's
    linear program.  Output is byte-identical to `re` / the trial walker.
    """

    def __init__(self, pattern: str, variants: List[_Variant],
                 scanner: Optional[ByteTableScanner],
                 regions0: List[Tuple[int, ByteTableScanner]],
                 num_caps: int):
        self.pattern = pattern
        self.variants = variants
        # scanner=None is UNPINNED mode: variant 0 keeps its trial ops and
        # is therefore authoritative for match/no-match on its own (its
        # language is a superset of the original, so walker-fail ⇒
        # original-fail); only region-validation failures need the exact
        # `re` net.  Pinned mode gates failed rows through the fused scan.
        self.scanner = scanner
        self.regions0 = regions0
        self.num_caps = num_caps
        self._re = re.compile(pattern.encode("latin-1"))

    def parse(self, arena: np.ndarray, offsets: np.ndarray,
              lengths: np.ndarray
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        offsets = np.asarray(offsets, dtype=np.int64)
        lengths = np.asarray(lengths, dtype=np.int32)
        n = len(offsets)
        C = max(self.num_caps, 1)
        if n == 0:
            return (np.zeros(0, dtype=bool),
                    np.zeros((0, C), dtype=np.int32),
                    np.full((0, C), -1, dtype=np.int32))

        v0 = self.variants[0]
        k_ok, k_off, k_len = v0.exec(arena, offsets, lengths)
        ok = k_ok if k_ok.dtype == np.bool_ else k_ok.astype(bool)
        if v0.cap_map == list(range(C)) and k_off.shape[1] == C:
            # variant 0 carries every original capture in order (the common
            # case): adopt the walker's freshly-allocated output arrays
            # instead of re-scattering ~2·n·C elements per parse
            cap_off, cap_len = k_off, k_len
        else:
            cap_off = np.zeros((n, C), dtype=np.int32)
            cap_len = np.full((n, C), -1, dtype=np.int32)
            for g, oc in enumerate(v0.cap_map):
                cap_off[:, oc] = k_off[:, g]
                cap_len[:, oc] = k_len[:, g]

        # regional validation of relaxed interiors (variant-0 rows only);
        # an absent optional region (span -1) has nothing to validate
        pend = ~ok
        region_fail = np.zeros(0, dtype=np.int64)
        rows = np.nonzero(ok)[0]
        for oc, rscan in self.regions0:
            if not len(rows):
                break
            present = cap_len[rows, oc] >= 0
            check = rows[present]
            tags = rscan.scan(arena, cap_off[check, oc].astype(np.int64),
                              cap_len[check, oc])
            bad_rows = check[(tags & 1) == 0]
            if len(bad_rows):
                pend[bad_rows] = True
                ok[bad_rows] = False
                region_fail = np.concatenate([region_fail, bad_rows])
                keep = np.ones(len(rows), dtype=bool)
                keep[np.searchsorted(rows, bad_rows)] = False
                rows = rows[keep]

        if self.scanner is None:
            # unpinned mode: the walker already decided match/no-match for
            # every row except the region-validation failures
            if len(region_fail):
                cap_off[region_fail] = 0
                cap_len[region_fail] = -1
                self._re_rows(arena, offsets, lengths, region_fail,
                              ok, cap_off, cap_len)
            return ok, cap_off, cap_len

        if pend.any():
            prows = np.nonzero(pend)[0]
            cap_off[prows] = 0
            cap_len[prows] = -1
            ok[prows] = False
            tags = self.scanner.scan(arena, offsets[prows], lengths[prows])
            defensive = prows[(tags & 1) == 1]
            for v in range(1, len(self.variants)):
                bit = np.uint32(1 << v)
                below = np.uint32((1 << v) - 1)
                sel = prows[((tags & bit) != 0) & ((tags & below) == 0)]
                if not len(sel):
                    continue
                var = self.variants[v]
                s_ok, s_off, s_len = var.exec(arena, offsets[sel],
                                              lengths[sel])
                s_ok = np.array(s_ok, dtype=bool)
                hit = sel[s_ok]
                for g, oc in enumerate(var.cap_map):
                    cap_off[hit, oc] = s_off[s_ok, g]
                    cap_len[hit, oc] = s_len[s_ok, g]
                ok[hit] = True
                # a tagged row whose walker disagreed is a bug net, not a
                # hot path: resolve it with re exactly
                defensive = np.concatenate([defensive, sel[~s_ok]])
            if len(defensive):
                self._re_rows(arena, offsets, lengths, defensive,
                              ok, cap_off, cap_len)
        return ok, cap_off, cap_len

    def _re_rows(self, arena, offsets, lengths, rows, ok, cap_off,
                 cap_len) -> None:
        for i in rows:
            o, ln = int(offsets[i]), int(lengths[i])
            m = self._re.fullmatch(bytes(arena[o:o + ln].tobytes()))
            if m is None:
                ok[i] = False
                cap_off[i] = 0
                cap_len[i] = -1
                continue
            ok[i] = True
            for g in range(self.num_caps):
                s, e = m.span(g + 1)
                if s >= 0:
                    cap_off[i, g] = o + s
                    cap_len[i, g] = e - s
                else:
                    cap_off[i, g] = 0
                    cap_len[i, g] = -1


def try_build_single(pattern: str) -> Optional[FusedSingleExec]:
    """Build the fused execution for one pattern, or None when the pattern
    does not profit (already linear) or cannot be handled exactly (the
    engine keeps its existing tiers — degradation, never breakage)."""
    if isinstance(pattern, bytes):
        pattern = pattern.decode("latin-1")
    try:
        re_c = re.compile(pattern.encode("latin-1"))
        tokens = strip_anchors(list(sre_parse.parse(pattern)))
        ast_root = _tok_to_ast(tokens)
    except Exception:  # noqa: BLE001 — unparseable/unsupported shapes
        # keep their existing tiers
        return None
    num_caps = re_c.groups
    relaxed = _expand_rngs(_relax_seq(ast_root, _END))
    choices: list = []
    rep_choices: list = []
    _collect_choices(relaxed, choices, rep_choices)
    n_variants = 1
    for _, k in choices:
        n_variants *= k
    rlx_nodes: list = []
    _walk_rlx(relaxed, rlx_nodes)
    if not rlx_nodes and n_variants == 1:
        return None                      # nothing to gain over the walker

    def _region_scanner(node: _FRlx) -> Tuple[int, ByteTableScanner]:
        rdfa = compile_dfa(_emit(node.region, None, False),
                           max_states=REGION_MAX_STATES,
                           max_classes=FUSED_MAX_CLASSES)
        return node.cap - 1, ByteTableScanner.from_dfa(rdfa)

    try:
        if rep_choices or n_variants > MAX_VARIANTS:
            # UNPINNED fallback: keep the trial ops in one relaxed walker.
            # Its language is a superset of the original, so walker-fail is
            # authoritative no-match; relaxed interiors are forced-boundary
            # spans, so walker-pass + region-pass is an exact match.  Only
            # region failures need the `re` net — no fused scan at all.
            if not rlx_nodes:
                return None
            caps: List[int] = []
            walker_str = _emit(relaxed, caps, True)
            wexec = try_build(compile_tier1(walker_str))
            if wexec is None:
                return None
            variants = [_Variant(walker_str, pattern, wexec,
                                 [c - 1 for c in caps])]
            regions0 = [_region_scanner(nd) for nd in rlx_nodes]
            return FusedSingleExec(pattern, variants, None, regions0,
                                   num_caps)

        variants: List[_Variant] = []
        regions0: List[Tuple[int, ByteTableScanner]] = []
        for assignment in itertools.product(
                *[range(k) for _, k in choices]) if choices else [()]:
            decisions = {id(node): c
                         for (node, _), c in zip(choices, assignment)}
            pinned = _pin(relaxed, decisions)
            caps = []
            walker_str = _emit(pinned, caps, True)
            exact_str = _emit(pinned, None, False)
            prog = compile_tier1(walker_str)
            wexec = try_build(prog)
            if wexec is None:
                return None              # host fused path needs the lib
            cap_map = [c - 1 for c in caps]
            variants.append(_Variant(walker_str, exact_str, wexec, cap_map))
            if len(variants) == 1:       # variant 0: regional validators
                v0_rlx: list = []
                _walk_rlx(pinned, v0_rlx)
                regions0 = [_region_scanner(nd) for nd in v0_rlx]
        # synthetic variant regexes: a budget demotion here just means "no
        # fused single-exec" (the pattern keeps its tier) — it must NOT
        # fire the user-facing demotion counter/alarm naming a regex the
        # user never wrote, neither now nor on a cache-hit replay
        fdfa = load_or_compile([v.exact for v in variants],
                               names=[f"v{i}" for i in
                                      range(len(variants))],
                               note_demotions=False)
        if fdfa.demoted:
            return None                  # variants must ALL be exact
    except Exception:  # noqa: BLE001 — Tier1Unsupported / DFAUnsupported /
        # FuseUnsupported / emit bugs all mean the same thing here: this
        # pattern keeps its existing tiers
        return None
    return FusedSingleExec(pattern, variants,
                           ByteTableScanner.from_fused(fdfa),
                           regions0, num_caps)


class FusedSetExec:
    """One fused automaton over a whole pattern SET (grok Match list,
    multiline start/continue/end): a single scan classifies every pattern
    at once.  Demoted members keep their per-pattern path; `bit_of` maps
    original set positions to accept-tag bits."""

    def __init__(self, patterns: Sequence[str],
                 names: Optional[Sequence[str]] = None):
        patterns = [p.decode("latin-1") if isinstance(p, bytes) else p
                    for p in patterns]
        self.patterns = patterns
        self.fdfa = load_or_compile(patterns, names=names)
        self.scanner = ByteTableScanner.from_fused(self.fdfa)
        self.bit_of: Dict[int, int] = {}
        nb = 0
        for i, p in enumerate(patterns):
            if nb < len(self.fdfa.patterns) and p == self.fdfa.patterns[nb]:
                self.bit_of[i] = nb
                nb += 1
        self._kernel = None
        self._kernel_lock = threading.Lock()

    @property
    def n_fused(self) -> int:
        return len(self.fdfa.patterns)

    def _device_kernel(self):
        with self._kernel_lock:
            if self._kernel is None:
                from ..kernels.dfa_scan import FusedScanKernel
                self._kernel = FusedScanKernel(self.fdfa)
            return self._kernel

    def classify(self, arena: np.ndarray, offsets: np.ndarray,
                 lengths: np.ndarray,
                 force: Optional[str] = None) -> np.ndarray:
        """uint32 accept-tag bitmask per row; bit b = fused member b
        full-matches.  The host's byte-table scanner decides unless
        `force="device"` asks for the dense device scan: that one is a
        synchronous call outside the dispatch window, 9.7 ms a 2048 x 256
        batch on the chip against the scanner's 0.27 (PERF.md section 7,
        ROADMAP A3), kept for the tests that hold the two to each other.
        The device form of the automaton earns its place as the scan stage
        of a fused pipeline program, which rides that program's window."""
        offsets = np.asarray(offsets, dtype=np.int64)
        lengths = np.asarray(lengths, dtype=np.int32)
        n = len(offsets)
        if n == 0:
            return np.zeros(0, dtype=np.uint32)
        if force != "device":
            return self.scanner.scan(arena, offsets, lengths)
        from ..device_batch import (LENGTH_BUCKETS, MAX_BATCH, pack_rows,
                                    pick_length_bucket)
        kern = self._device_kernel()
        tags = np.zeros(n, dtype=np.uint32)
        max_bucket = LENGTH_BUCKETS[-1]
        over = lengths > max_bucket
        device_idx = np.nonzero(~over)[0]
        for i in range(0, len(device_idx), MAX_BATCH):
            chunk = device_idx[i:i + MAX_BATCH]
            d_len = lengths[chunk]
            L = pick_length_bucket(int(d_len.max()) if len(d_len) else 1) \
                or max_bucket
            batch = pack_rows(arena, offsets[chunk], d_len, L)
            # synchronous chunked classify tier — callers that want the
            # resident form use the fused pipeline scan stage instead
            # loonglint: disable=host-bounce
            k_tags = np.asarray(kern(batch.rows, batch.lengths))
            tags[chunk] = k_tags[: len(chunk)].astype(np.uint32)
        over_idx = np.nonzero(over)[0]
        if len(over_idx):
            tags[over_idx] = self.scanner.scan(arena, offsets[over_idx],
                                               lengths[over_idx])
        return tags

    def member_masks(self, tags: np.ndarray
                     ) -> List[Optional[np.ndarray]]:
        """Per ORIGINAL set position: bool match array, or None when the
        member was demoted (caller keeps its per-pattern path)."""
        out: List[Optional[np.ndarray]] = []
        for i in range(len(self.patterns)):
            bit = self.bit_of.get(i)
            if bit is None:
                out.append(None)
            else:
                out.append((tags & np.uint32(1 << bit)) != 0)
        return out


def try_build_set(patterns: Sequence[str],
                  names: Optional[Sequence[str]] = None
                  ) -> Optional[FusedSetExec]:
    """FusedSetExec, or None when nothing in the set can fuse."""
    try:
        return FusedSetExec(patterns, names=names)
    except Exception:  # noqa: BLE001 — any compile failure means "no fusion"
        return None


# ---------------------------------------------------------------------------
# find-all masking: the pattern pairs the device's ``mask`` stage takes
# ---------------------------------------------------------------------------

#: a prefix expands to at most this many fixed-length alternatives, each at
#: most this long — one shifted compare per byte a walk step
MASK_MAX_ALTERNATIVES = 16
MASK_MAX_PREFIX = 64


@dataclass
class MaskPlan:
    """A masking pair ``(B)C`` in the form the device's ``mask`` stage runs
    (``ops/kernels/dfa_scan.py`` `build_span_mask_fn`): ``alternatives``,
    the fixed-length byte-class sequences B matches, in the order ``re``
    tries them; ``content``, C's one byte class, and its count bounds
    ``lo`` .. ``hi`` (None: unbounded), taken greedily; ``slots``, the
    matches a row hands back (1 where only the first is replaced,
    ``replace_all`` False)."""

    alternatives: List[List[np.ndarray]]
    content: np.ndarray
    lo: int
    hi: Optional[int]
    slots: int
    replace_all: bool = True

    def ident(self) -> list:
        """The plan's content identity, for the program cache."""
        return [[[CharClass(m).to_regex_fragment() for m in alt]
                 for alt in self.alternatives],
                CharClass(self.content).to_regex_fragment(),
                self.lo, self.hi, self.slots, self.replace_all]


def _fixed_alternatives(items, dotall: bool) -> Optional[List[list]]:
    """The byte-class sequences a parsed sre item list matches, each of a
    fixed length, in ``re``'s order of trying (a sequence tries its first
    part's alternatives outermost); None where some part has no fixed
    length, sets a flag, or the expansion passes the caps."""
    alts: List[list] = [[]]
    for op, av in items:
        if op is sre_c.LITERAL:
            if av > 255:
                return None
            piece = [[CharClass.single(av).mask]]
        elif op is sre_c.NOT_LITERAL:
            if av > 255:
                return None
            piece = [[~CharClass.single(av).mask]]
        elif op is sre_c.IN:
            try:
                piece = [[CharClass.from_sre_in(av).mask]]
            except ValueError:
                return None
        elif op is sre_c.ANY:
            piece = [[CharClass.dot(dotall).mask]]
        elif op is sre_c.SUBPATTERN:
            _group, add_flags, del_flags, sub = av
            if add_flags or del_flags:
                return None
            piece = _fixed_alternatives(sub, dotall)
        elif op is sre_c.BRANCH:
            piece = []
            for branch in av[1]:
                got = _fixed_alternatives(branch, dotall)
                if got is None:
                    return None
                piece += got
        elif op in (sre_c.MAX_REPEAT, sre_c.MIN_REPEAT) and av[0] == av[1]:
            body = _fixed_alternatives(av[2], dotall)
            if body is None:
                return None
            piece = [[]]
            for _ in range(av[0]):
                piece = [a + b for a in piece for b in body]
                if len(piece) > MASK_MAX_ALTERNATIVES:
                    return None
        else:
            return None
        if piece is None:
            return None
        alts = [a + p for a in alts for p in piece]
        if len(alts) > MASK_MAX_ALTERNATIVES \
                or any(len(a) > MASK_MAX_PREFIX for a in alts):
            return None
    return alts


def try_build_mask(before: bytes, content: bytes, slots: int,
                   replace_all: bool = True) -> Optional[MaskPlan]:
    """The device form of upstream's masking pair — ``(before)content``,
    each match's ``content`` part replaced — or None where the device tier
    does not take it: every match of ``before`` must have one length for
    each of its alternatives (literals, alternations of literals, byte
    classes, fixed counts; no flags), none empty, and ``content`` must be
    ONE byte class under a greedy ``+``, ``*``, ``?`` or ``{m,n}`` (or
    alone).  Then leftmost-first, non-overlapping matching is: at the
    leftmost start at or after the cursor, the first alternative whose
    content count reaches ``lo``, content as long as the class runs (at most
    ``hi``) — what ``re`` and RE2 give for such a pair."""
    try:
        b_parsed = sre_parse.parse(before)
        c_parsed = sre_parse.parse(content)
    except (re.error, TypeError, ValueError):
        return None
    flags = b_parsed.state.flags | c_parsed.state.flags
    if flags & (sre_c.SRE_FLAG_IGNORECASE | sre_c.SRE_FLAG_LOCALE):
        return None
    dotall = bool(flags & sre_c.SRE_FLAG_DOTALL)
    alts = _fixed_alternatives(list(b_parsed), dotall)
    if not alts or any(len(a) == 0 for a in alts):
        return None
    items = list(c_parsed)
    if len(items) != 1:
        return None
    op, av = items[0]
    if op is sre_c.MAX_REPEAT:
        lo, hi, body = av
        body = list(body)
        if len(body) != 1:
            return None
        op, av = body[0]
    elif op in (sre_c.LITERAL, sre_c.NOT_LITERAL, sre_c.IN, sre_c.ANY):
        lo = hi = 1
    else:
        return None
    one = _fixed_alternatives([(op, av)], dotall)
    if one is None or len(one) != 1 or len(one[0]) != 1:
        return None
    return MaskPlan(alts, one[0][0], int(lo),
                    None if hi is sre_c.MAXREPEAT else int(hi),
                    int(slots) if replace_all else 1, bool(replace_all))

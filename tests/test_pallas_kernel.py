"""Pallas-fused Tier-1 kernel: differential equivalence vs the XLA path.

The Pallas kernel body IS build_extract_core — the same walk the XLA path
jits — so any divergence here means the pallas_call plumbing (blocking,
state layout, output dtypes) broke semantics. Runs in interpreter mode on
CPU (compiled Mosaic needs real TPU hardware): every construction here
passes ``interpret=True`` — the package never infers it from the backend.
"""

import ast
import pathlib
import re

import numpy as np
import pytest

from loongcollector_tpu.ops.device_batch import pack_rows, pick_length_bucket
from loongcollector_tpu.ops.kernels.field_extract import ExtractKernel
from loongcollector_tpu.ops.kernels.field_extract_pallas import (
    PallasExtractKernel, _pick_block_rows)
from loongcollector_tpu.ops.regex.program import compile_tier1

APACHE = (r'(\S+) (\S+) (\S+) \[([^\]]+)\] '
          r'"(\S+) (\S+) ([^"]*)" (\d{3}) (\d+)')

# Cover every op family: literals, spans, fixed spans, optional groups,
# alternation, counted repeats, and a pivot (ambiguous span) program.
PATTERNS = [
    APACHE,
    r"(\d+)-(\w+)",
    r"(a+)(?: opt(\d+))? end",                      # optional group
    r"(cat|dog|bird) says (\S+)",                   # alternation
    r"(\d{3}) fixed",                               # counted repeat
    r"pre (.*) post",                               # pivot: ambiguous span
    r"\[([^\]]*)\] (.*)",                           # pivot with class prefix
]


def _inputs_for(pattern: str):
    rng = np.random.default_rng(hash(pattern) % 2**31)
    rx = re.compile(pattern.encode())
    lines = []
    # matching inputs built from the apache generator or simple templates
    seeds = [
        b'1.2.3.4 - frank [10/Oct/2000:13:55:36 -0700] "GET /a HTTP/1.0" 200 23',
        b"123-abc", b"aaa opt7 end", b"aaa end", b"cat says hi",
        b"dog says x", b"421 fixed", b"pre middle bit post",
        b"[tag] rest of line", b"pre  post",
    ]
    lines += [s for s in seeds]
    # non-matching noise
    for _ in range(40):
        n = int(rng.integers(0, 40))
        lines.append(bytes(rng.integers(32, 127, n, dtype=np.uint8)))
    # label each line by the CPU oracle so the test is self-checking
    return [(ln, rx.fullmatch(ln)) for ln in lines if ln]


@pytest.mark.parametrize("pattern", PATTERNS)
def test_pallas_matches_xla_and_re(pattern):
    prog = compile_tier1(pattern)
    xla = ExtractKernel(prog)
    pallas = PallasExtractKernel(prog, interpret=True)
    labelled = _inputs_for(pattern)
    lines = [ln for ln, _ in labelled]
    arena = np.frombuffer(b"".join(lines), dtype=np.uint8)
    lens = np.array([len(l) for l in lines], np.int32)
    offs = np.concatenate([[0], np.cumsum(lens[:-1])]).astype(np.int64)
    L = pick_length_bucket(int(lens.max()))
    batch = pack_rows(arena, offs, lens, L)

    ok_x, off_x, len_x = (np.asarray(a) for a in
                          xla(batch.rows, batch.lengths))
    ok_p, off_p, len_p = (np.asarray(a) for a in
                          pallas(batch.rows, batch.lengths))
    np.testing.assert_array_equal(ok_x, ok_p)
    np.testing.assert_array_equal(off_x, off_p)
    np.testing.assert_array_equal(len_x, len_p)

    # and both agree with the `re` oracle
    for i, (ln, m) in enumerate(labelled):
        assert bool(ok_p[i]) == (m is not None), (pattern, ln)
        if m:
            for g in range(m.re.groups):
                s, e = m.span(g + 1)
                if s < 0:
                    assert len_p[i, g] == -1
                else:
                    assert (off_p[i, g], len_p[i, g]) == (s, e - s)


def test_block_rows_divide_batch():
    """Block sizing must always divide the (power-of-two) batch."""
    for B in (256, 512, 4096, 65536):
        for L in (128, 512, 4096):
            bB = _pick_block_rows(B, L, n_masks=12)
            assert B % bB == 0
            assert bB >= 32


def test_engine_pallas_env_override(monkeypatch):
    """LOONG_PALLAS=1 routes parse_batch through the Pallas kernel."""
    monkeypatch.setenv("LOONG_PALLAS", "1")
    from loongcollector_tpu.ops.regex.engine import RegexEngine
    eng = RegexEngine(r"(\d+)/(\w+)")
    # off-chip the engine's own (compiled) Pallas kernel fails loudly, so
    # the test hands it the interpreted one the engine would otherwise build
    eng._pallas_kernel = PallasExtractKernel(eng._segment_kernel.program,
                                             interpret=True)
    lines = [b"12/ab", b"nope", b"7/z"]
    arena = np.frombuffer(b"".join(lines), dtype=np.uint8)
    lens = np.array([len(l) for l in lines], np.int32)
    offs = np.concatenate([[0], np.cumsum(lens[:-1])]).astype(np.int64)
    res = eng.parse_batch(arena, offs, lens)
    assert eng._pallas_kernel is not None
    assert list(res.ok) == [True, False, True]
    # spans are arena-absolute
    assert (res.cap_off[2, 0], res.cap_len[2, 0]) == (9, 1)


def test_engine_pallas_off_chip_fails_loudly(monkeypatch):
    """LOONG_PALLAS=1 on a CPU backend must not quietly interpret: the
    compiled kernel is asked for and jax refuses it."""
    monkeypatch.setenv("LOONG_PALLAS", "1")
    from loongcollector_tpu.ops.regex.engine import RegexEngine
    eng = RegexEngine(r"(\d+)/(\w+)")
    kern = eng._single_device_kernel()
    assert isinstance(kern, PallasExtractKernel)
    rows = np.zeros((256, 128), np.uint8)
    with pytest.raises(ValueError, match="interpret mode"):
        kern(rows, np.zeros(256, np.int32))


def test_no_module_infers_interpret_from_backend():
    """``interpret`` is passed by tests, never inferred: nothing under
    loongcollector_tpu/ may compute an interpret flag from the backend."""
    pkg = pathlib.Path(__file__).resolve().parent.parent / "loongcollector_tpu"
    backend_words = ("default_backend", "devices(", "platform", "JAX_PLATFORMS")
    offenders = []
    for path in sorted(pkg.rglob("*.py")):
        src = path.read_text()
        if "interpret" not in src:
            continue
        for node in ast.walk(ast.parse(src)):
            value = None
            if isinstance(node, ast.keyword) and node.arg \
                    and "interpret" in node.arg:
                value = node.value
            elif isinstance(node, (ast.Assign, ast.AnnAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                if any("interpret" in ast.unparse(t) for t in targets):
                    value = node.value
            if value is not None and any(
                    w in ast.unparse(value) for w in backend_words):
                offenders.append(f"{path.relative_to(pkg)}:{node.value.lineno}")
    assert not offenders, offenders

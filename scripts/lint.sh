#!/usr/bin/env bash
# Repo lint gate — everything here also runs under tier-1 (the loonglint
# scan and the stress tests are pytest-gated), so this script is the fast
# local entry point, not the only enforcement.
#
#   1. loonglint: AST invariant checks over loongcollector_tpu/
#      (docs/static_analysis.md);
#   2. native hygiene: -Werror syntax pass + clang-tidy when installed;
#   3. ResourceWarning sweep: the concurrency stress tests under
#      `python -X dev -W error::ResourceWarning` — an unclosed socket,
#      file, or thread-local leak in the hot paths fails loudly here;
#   4. tracing-overhead smoke: loongtrace's disabled path must stay one
#      branch per hook (10k-event synthetic pipeline, disabled vs no-op
#      baseline, >5% regression fails — docs/observability.md).
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== loonglint =="
# --budget caps the 14-checker sweep's own wall clock: the static gate
# stays a fast-feedback tool, and a checker that regresses to quadratic
# work fails here before it annoys every future lint run (per-checker
# timings: `python -m loongcollector_tpu.analysis --json` checker_seconds)
python -m loongcollector_tpu.analysis --budget 30 "$@"

echo "== tracing-overhead smoke =="
JAX_PLATFORMS=cpu python scripts/trace_overhead.py

echo "== profiler-overhead smoke (loongprof) =="
# with LOONG_PROF off the marker hooks must stay one branch per hook —
# same disabled-vs-noop-baseline >5% paired-min gate as the trace smoke
JAX_PLATFORMS=cpu python scripts/prof_overhead.py

echo "== ledger-overhead smoke (loongledger) =="
# with LOONG_LEDGER off the conservation-accounting hooks must stay one
# branch per hook — same paired-min >5% gate as the trace/prof smokes
JAX_PLATFORMS=cpu python scripts/ledger_overhead.py

echo "== slo-overhead smoke (loongslo) =="
# with LOONG_SLO off the ingest-stamp / terminal-observe hooks must stay
# one branch per hook — same paired-min >5% gate as the other planes
JAX_PLATFORMS=cpu python scripts/slo_overhead.py

echo "== xprof-overhead smoke (loongxprof) =="
# with LOONG_XPROF off the device-timeline hooks must stay one branch per
# hook on the dispatch hot path — same paired-min >5% gate, measured on a
# real DevicePlane submit/result loop
JAX_PLATFORMS=cpu python scripts/xprof_overhead.py

echo "== multi-worker smoke (loongshard) =="
# the disabled-trace overhead gate and the metric-naming checker must hold
# with the sharded plane active (LOONG_PROCESS_THREADS=4): the overhead
# budget is per-hook regardless of worker count, and every worker-owned
# metric record must still obey the naming/ownership rules
JAX_PLATFORMS=cpu LOONG_PROCESS_THREADS=4 python scripts/trace_overhead.py
LOONG_PROCESS_THREADS=4 python -m loongcollector_tpu.analysis \
    --checks metric-naming

echo "== columnar equivalence gate (loongcolumn) =="
# default pipeline chains through the columnar fast path AND the dict
# path; any sink-payload byte difference (or any per-event object minted
# on the columnar side) fails — docs/observability.md "Columnar event path"
JAX_PLATFORMS=cpu python scripts/columnar_equivalence.py

echo "== fused-DFA equivalence gate (loongfuse) =="
# the fused multi-accept automaton must classify EXACTLY like per-pattern
# `re` over the default grok set + multiline classics — any disagreement
# means fusion would mis-gate extraction
JAX_PLATFORMS=cpu python scripts/fuse_equivalence.py

echo "== fused-pipeline equivalence gate (loongresident) =="
# the same processor chain with stage fusion ON (one fused device program
# per batch slot) and OFF (per-stage dispatch) must produce byte-identical
# groups across the regex / grok / delimiter / json / multiline families —
# fusion is an execution-plan change, never a semantics change
JAX_PLATFORMS=cpu python scripts/resident_equivalence.py

echo "== structural-index equivalence gate (loongstruct) =="
# the native/numpy/device structural bitmaps must be bit-identical, the
# JSON plane must match Python `json` row-for-row, and quote-mode
# delimiter parsing must reproduce the reference CSV FSM + python csv —
# any span or byte diff fails
JAX_PLATFORMS=cpu python scripts/struct_equivalence.py

echo "== aggregation equivalence gate (loongagg) =="
# the native/numpy/device segment-reduce substrates must agree (numpy
# bit-identical incl. f64 sums, device exact on selections/counts), and
# the full rollup aggregator must emit byte-identical groups over the
# columnar and per-event dict paths
JAX_PLATFORMS=cpu python scripts/agg_equivalence.py

echo "== reload-soak smoke (loongtenant) =="
# sustained config churn under sustained ingest with the live ledger +
# auditor: any nonzero tenant residual, lost event, or failed reload of a
# valid config exits nonzero (docs/robustness.md "Hot reload")
JAX_PLATFORMS=cpu python scripts/reload_soak.py \
    --tenants 4 --rate 5 --seconds 3

echo "== crash-storm smoke (loongcrash) =="
# one seeded SIGKILL of the real agent at the send boundary, then restart
# + drain: zero loss byte-for-byte, duplicates bounded, ledger residual 0
# (docs/robustness.md "Crash durability"; full 8-seed matrix in soak.sh)
JAX_PLATFORMS=cpu python scripts/crash_storm.py --seed 3 --lines 120

echo "== native lint =="
make -C native lint

echo "== native sanitizer plane (ASan+UBSan) =="
# instrumented rebuild of the data plane driven through the native test
# corpus + the four equivalence gates (scripts/sanitize.sh); probe-gated
# so boxes without g++/libasan still lint
if scripts/sanitize.sh --probe >/dev/null 2>&1; then
    scripts/sanitize.sh
else
    echo "no sanitizer toolchain; skipped (scripts/sanitize.sh --probe)"
fi

echo "== ResourceWarning sweep (concurrency stress) =="
JAX_PLATFORMS=cpu python -X dev -W error::ResourceWarning -m pytest \
    tests/test_concurrency_stress.py tests/test_queues.py \
    -q -m 'not slow' -p no:cacheprovider

echo "lint OK"

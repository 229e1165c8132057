#!/usr/bin/env bash
# Native sanitizer plane (ISSUE 16): build the C++ data plane under
# ASan+UBSan (default) or TSan (--tsan), point the ctypes bridge at the
# instrumented libraries via LOONG_NATIVE_LIB / LOONG_EBPF_DRIVER, and
# drive the native test corpus plus the four native-exercising
# equivalence gates through them.  Any sanitizer report is fatal:
# recovery is compiled out (-fno-sanitize-recover=all) and halt_on_error
# aborts the process, so a clean exit MEANS no reports.
#
# Python loads the instrumented .so into an uninstrumented interpreter,
# which requires the sanitizer runtime preloaded before libc
# (LD_PRELOAD); leak detection stays off because CPython itself holds
# allocations for the process lifetime and would drown the exit report.
#
#   scripts/sanitize.sh            ASan+UBSan: native corpus + gates
#   scripts/sanitize.sh --tsan     TSan variant (native corpus only —
#                                  opt-in, slower, and the gates run the
#                                  same single-threaded entry points)
#   scripts/sanitize.sh --probe    exit 0 iff the toolchain can build
#                                  and preload sanitized libraries
set -euo pipefail
cd "$(dirname "$0")/.."

CXX="${CXX:-g++}"

probe() {
    command -v "$CXX" >/dev/null 2>&1 || return 1
    command -v make >/dev/null 2>&1 || return 1
    local asan
    asan="$("$CXX" -print-file-name=libasan.so 2>/dev/null)" || return 1
    # an unresolved runtime echoes the bare name back
    [ -e "$asan" ] || return 1
    return 0
}

if [ "${1:-}" = "--probe" ]; then
    probe || { echo "sanitize: no usable sanitizer toolchain"; exit 1; }
    echo "sanitize: toolchain OK ($CXX + libasan)"
    exit 0
fi

probe || {
    echo "sanitize: no usable sanitizer toolchain ($CXX/libasan missing)"
    exit 1
}

VARIANT=asan
if [ "${1:-}" = "--tsan" ]; then
    VARIANT=tsan
fi

echo "== sanitize: building native plane ($VARIANT) =="
make -C native "$VARIANT"

BUILD_DIR="$PWD/native/build/$VARIANT"
export LOONG_NATIVE_LIB="$BUILD_DIR/libloongcollector_native.so"
export LOONG_EBPF_DRIVER="$BUILD_DIR/libloong_ebpf_sim.so"
export JAX_PLATFORMS=cpu

if [ "$VARIANT" = tsan ]; then
    RUNTIMES="$("$CXX" -print-file-name=libtsan.so)"
    export TSAN_OPTIONS="halt_on_error=1 report_thread_leaks=0"
else
    RUNTIMES="$("$CXX" -print-file-name=libasan.so)"
    UBSAN_RT="$("$CXX" -print-file-name=libubsan.so)"
    [ -e "$UBSAN_RT" ] && RUNTIMES="$RUNTIMES $UBSAN_RT"
    export ASAN_OPTIONS="detect_leaks=0:halt_on_error=1"
    export UBSAN_OPTIONS="halt_on_error=1:print_stacktrace=1"
fi
export LD_PRELOAD="$RUNTIMES"

echo "== sanitize: native test corpus ($VARIANT) =="
python -m pytest tests/test_native.py tests/test_native_t1.py \
    tests/test_parse_timestamp_columns.py -q -p no:cacheprovider

if [ "$VARIANT" = tsan ]; then
    echo "sanitize OK (tsan)"
    exit 0
fi

# the four equivalence gates cross-check every native entry point
# against the numpy/python substrates — under ASan they double as a
# memory-safety sweep of the exact byte patterns the gates generate
echo "== sanitize: structural-index equivalence =="
python scripts/struct_equivalence.py

echo "== sanitize: fused-DFA equivalence =="
python scripts/fuse_equivalence.py

echo "== sanitize: columnar equivalence =="
python scripts/columnar_equivalence.py

echo "== sanitize: aggregation equivalence =="
python scripts/agg_equivalence.py

echo "sanitize OK (asan+ubsan)"

#!/usr/bin/env python3
"""perfbench/run.py — one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on the machine that holds the chip.  The
last line of standard output is the result object; earlier lines say where
set-up went and give the per-second series.  Every number compared for
``correct`` is printed beside its limit as the last lines of standard error
and under ``checks`` in the result.  Exit code 0 with a result line, another
code and no result line when the run could not be made (no accelerator, no
program, an agent that died).

``--fault`` is for the controls (perfbench/README.md); the driver never
passes it.
"""

import time

T_START = time.monotonic()          # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import harness, spec  # noqa: E402
from benchlib.agent import AgentFailure  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None,
                    help="control: plant a fault (drop_row, dup_row, "
                         "swap_rows, alter_field, time_off, stall, residual)")
    args = ap.parse_args(argv)
    try:
        return harness.run_cell(args, T_START)
    except (harness.RunFailure, AgentFailure, spec.SpecError) as e:
        print(f"perfbench FAILED: {e}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""perfbench — one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Stands the cell's deployment up from the seed, warms it, measures for
`--seconds`, checks what the window produced against the plain
reference, and prints one JSON object as the last line of standard
output: `--trace 0` the cell's end-to-end metrics, `--trace 1` its
per-layer metrics and a breakdown of the traced slice.  Exits non-zero,
with no such line, without the chips the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse     # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="perfbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from perfbench.harness.cell import run_cell
    return run_cell(args.workload, args.seed, args.seconds,
                    bool(args.trace), T_START)


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""A cell's run with one fault planted under it (perfbench/faults.py):
the control that `correct` has to fail.  Not part of a check; the
builder runs it on the chip at the cell's own size.

    python3 perfbench/control.py --fault <name> --workload <name> --seed <n> --seconds <s>

Prints the same result line as perfbench/run.py; `correct` is expected
to read false.  `--parked <name>` runs a cell whose entries wait in
perfbench/parked/<name>.json; `--fault none` plants nothing; `--mended`
puts jerasure's coding matrix under the program's reed_sol_van
(perfbench/faults.py).
"""

import time

T_START = time.perf_counter()

import argparse     # noqa: E402
import contextlib   # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from perfbench import faults
    ap = argparse.ArgumentParser(prog="perfbench-control")
    ap.add_argument("--fault", required=True, choices=["none"] + sorted(
        f for per in faults.FAULTS.values() for f in per))
    ap.add_argument("--parked", default="")
    ap.add_argument("--mended", action="store_true")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import ceph_tpu  # noqa: F401
    from perfbench.harness.cell import run_cell
    with contextlib.ExitStack() as stack:
        if args.mended:
            stack.enter_context(faults.mended())
        if args.fault != "none":
            stack.enter_context(faults.plant(args.fault))
        return run_cell(args.workload, args.seed, args.seconds, False,
                        T_START, parked=args.parked)


if __name__ == "__main__":
    sys.exit(main())

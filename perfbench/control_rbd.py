#!/usr/bin/env python3
"""perfbench/control.py for the rbd cells: a cell's run with one of
perfbench/faults_rbd.py's faults planted under it, the control that
`correct` has to fail.  Not part of a check; the builder runs it on the
chip at the cell's own size.

    python3 perfbench/control_rbd.py --fault <name> --workload <name> --seed <n> --seconds <s>
"""

import time

T_START = time.perf_counter()

import argparse     # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from perfbench import faults_rbd
    ap = argparse.ArgumentParser(prog="perfbench-control-rbd")
    ap.add_argument("--fault", required=True, choices=sorted(
        f for per in faults_rbd.FAULTS.values() for f in per))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import ceph_tpu  # noqa: F401
    from perfbench.harness.cell import run_cell
    with faults_rbd.plant(args.fault):
        return run_cell(args.workload, args.seed, args.seconds, False,
                        T_START)


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` — run one cell of BENCHMARK.json on the chips it names and
print the result as the last line of stdout.  One process, the only one to
touch JAX; never falls back to a CPU (see README.md)."""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from perfbench import harness
    rc, _ = harness.run_cell(args.workload, args.seed, args.seconds,
                             args.trace, t_process_start=T_PROCESS_START)
    return rc


if __name__ == "__main__":
    sys.exit(main())

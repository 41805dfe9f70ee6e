"""Run one cell of the benchmark and print its result line.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  The cell, its configuration, its traffic and its metrics
are found by the names in ``BENCHMARK.json``.  The run loads and warms up
(``setup_s``), measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints one JSON object as the
last line of its standard output.  Without a TPU it exits non-zero and
prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if args.seed < 0:
        sys.exit("bench: --seed must be a whole number >= 0")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit(f"bench: no src/repro under {ROOT}; run from a checkout of "
                 "the repository")
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness import core
    cell = core.Cell(args.workload)
    devices = core.require_accelerator(cell.chips)
    core.enable_compile_cache(ROOT)
    core.run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START,
                  devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())

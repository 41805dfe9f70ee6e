"""Readings from which the limits of `correct` are set.

  python bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 30 [--control]

Runs the cell once per seed in one process, as ``bench/run.py`` would but
with the given window, and prints one JSON line per seed: the numbers the
run compared (the program's readings), and with ``--control`` the same
numbers for the control, the plain reference put in the program's place
one precision lower (``control`` of the cell's system), judged by the
cell's own limits as ``control_correct``, which has to come out false.
The benchmark's own runs never run the control.  Needs the chips the
cell asks for.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def readings(cell, system, seed: int, seconds: float, devices, counter,
             control: bool):
    """One seed's line: the program's compared numbers, and the control's
    judged by the same limits."""
    from harness import core
    r = core.Run(cell, seed, seconds, False, time.time(), devices, counter)
    system.run(r)
    line = {"seed": seed, "correct": r.correct, "program": {
        k: v["value"] for k, v in r.compared.items()}}
    if control:
        ctl = system.control(r)
        line["control"] = ctl
        line["control_correct"] = all(
            v <= r.compared[k]["limit"] for k, v in ctl.items())
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness import core
    cell = core.Cell(args.workload)
    devices = core.require_accelerator(cell.chips)
    core.enable_compile_cache(ROOT)
    system = cell.system()
    counter = core.CompileCounter()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, system, seed, args.seconds, devices,
                                  counter, args.control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

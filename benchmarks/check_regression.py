"""Perf-regression gate: compare a fresh BENCH_sim.json against the
committed baseline (benchmarks/perf_baseline.json).

Fails (exit 1) when aggregate engine throughput regresses by more than
``--max-regression`` (default 25%) — the nightly CI job runs this right
after the benchmark smoke, so a PR that slows the simulator fleet turns
the run red instead of silently drifting.  The gate compares
``steps_per_sec_steady`` (compile time excluded) when both sides have
it, so an XLA-cache miss — every ``src/repro`` change invalidates the
CI cache key — cannot masquerade as an engine regression; it falls back
to ``steps_per_sec`` for older baselines.

The env fingerprint is a RUNNER CLASS, not raw hardware: CI sets
``PERF_RUNNER_CLASS`` (nightly and refresh-baseline use the same
value), local runs default to ``cpu<count>``.  Matching class + matching
SIM_DEVICES arms the gate (fail-loud); anything else skips with a
notice, because comparing against a baseline from different hardware
gates the machine, not the change.

Refresh the baseline after an intentional perf change with::

    python benchmarks/run.py --fast
    python benchmarks/check_regression.py --update

(or dispatch the ``refresh-baseline`` CI workflow, which runs both on
the hosted-runner class and uploads the artifact to commit).  The
committed baseline records where it was actually measured in
``measured_on``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _env_fingerprint() -> dict:
    """What the throughput numbers depend on besides the code: comparing
    against a baseline from different hardware gates the machine, not
    the change.  The class is an explicit label (PERF_RUNNER_CLASS, set
    by CI) so a baseline built FOR the hosted-runner class arms the
    nightly gate; without the label it falls back to the host's CPU
    count, keeping ad-hoc local comparisons honest."""
    return {"class": (os.environ.get("PERF_RUNNER_CLASS")
                      or f"cpu{os.cpu_count()}"),
            "sim_devices": os.environ.get("SIM_DEVICES", "")}

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DEFAULT_CURRENT = os.path.join(_ROOT, "BENCH_sim.json")
DEFAULT_BASELINE = os.path.join(_ROOT, "benchmarks", "perf_baseline.json")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--current", default=DEFAULT_CURRENT,
                   help="fresh BENCH_sim.json (from benchmarks/run.py)")
    p.add_argument("--baseline", default=DEFAULT_BASELINE,
                   help="committed baseline json")
    p.add_argument("--max-regression", type=float, default=0.25,
                   help="allowed fractional steps_per_sec drop (0.25=25%%)")
    p.add_argument("--update", action="store_true",
                   help="rewrite the baseline from --current and exit")
    args = p.parse_args(argv)

    with open(args.current) as f:
        cur = json.load(f)

    if args.update:
        base = {k: cur[k] for k in
                ("preset", "trace_len", "num_sims", "steps_per_sec",
                 "steps_per_sec_steady", "sim_wall_s_total",
                 "figures_wall_s") if k in cur}
        base["stages"] = cur.get("stages", {})
        base["env"] = _env_fingerprint()
        # provenance: where the numbers were ACTUALLY measured (the env
        # class above is the intended comparison target)
        base["measured_on"] = {"cpu_count": os.cpu_count(),
                               "sim_devices": os.environ.get(
                                   "SIM_DEVICES", "")}
        with open(args.baseline, "w") as f:
            json.dump(base, f, indent=1)
        print(f"baseline updated: {args.baseline} "
              f"(steps_per_sec={base['steps_per_sec']})")
        return 0

    with open(args.baseline) as f:
        base = json.load(f)

    if cur.get("preset") != base.get("preset"):
        print(f"preset mismatch (current={cur.get('preset')} "
              f"baseline={base.get('preset')}); skipping gate")
        return 0
    env = _env_fingerprint()
    if base.get("env") != env:
        print(f"environment mismatch (current={env} "
              f"baseline={base.get('env')}); skipping gate — refresh the "
              "baseline on this runner class with --update")
        return 0

    metric = ("steps_per_sec_steady"
              if "steps_per_sec_steady" in cur
              and "steps_per_sec_steady" in base else "steps_per_sec")
    b, c = float(base[metric]), float(cur[metric])
    drop = 1.0 - c / b if b else 0.0
    print(f"{metric}: baseline={b:.1f} current={c:.1f} "
          f"delta={-drop * 100:+.1f}%")
    for k in ("figures_wall_s", "sim_wall_s_total"):
        if k in cur and k in base:
            print(f"{k}: baseline={base[k]} current={cur[k]}")
    if drop > args.max_regression:
        print(f"FAIL: {metric} regressed {drop * 100:.1f}% "
              f"(limit {args.max_regression * 100:.0f}%)")
        return 1
    print("perf gate OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())

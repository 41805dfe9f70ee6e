"""Real-trace validation: pin the synthetic generators to ground truth.

Replays each committed fixture trace (a real-format ChampSim / lackey
file under ``tests/fixtures/traces/``) SIDE BY SIDE with its matched
Table-II synthetic generator — both as lanes of ONE
:func:`repro.sim.simulate_batch` dispatch, since they share the machine
shape — and emits a miss-rate / PTW-latency comparison table:

  * radix L1-DTLB miss rate, PTE L1 hit rate, data L1 miss rate
  * radix average page-table-walk latency (cycles)
  * NDPage end-to-end speedup vs radix

The table lands in ``BENCH_sim.json`` under a ``"real_traces"`` key
(merged into the existing file, never clobbering the figure/sweep
sections), so nightly CI tracks how far the synthetics drift from the
real traces run over run.  Structural checks fail the run: every side
must be translation-intensive (L1-TLB miss rate >= 10% — the property
the paper's whole evaluation rests on) and NDPage must not lose to
radix on a REAL trace (>= 1.0).

Usage:
  python benchmarks/trace_validate.py [--fast] [--cores N]
  python benchmarks/run.py --trace-validate      # same, as a stage
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Tuple

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

FIXTURE_DIR = os.path.join("tests", "fixtures", "traces")

#: (pair name, fixture file, matched synthetic workload)
DEFAULT_PAIRS: Tuple[Tuple[str, str, str], ...] = (
    ("gups", "gups_small.champsim.xz", "rnd"),
    ("graph", "graph_small.lackey.gz", "bc"),
)

Row = Tuple[str, float, str]


def _metrics(res) -> Dict[str, float]:
    return {
        "accesses": int(res.accesses),
        "tlb_miss_rate": round(res.scalar("tlb_miss_rate", "radix"), 4),
        "pte_l1_miss_rate": round(
            res.scalar("pte_l1_miss_rate", "radix"), 4),
        "data_l1_miss_rate": round(
            res.scalar("data_l1_miss_rate", "radix"), 4),
        "radix_ptw_cyc": round(
            res.scalar("avg_ptw_latency", "radix"), 1),
        "ndpage_speedup": round(res.speedup_vs()["ndpage"], 4),
    }


def run_validation(pairs=DEFAULT_PAIRS, fast: bool = True,
                   cores: int = 2) -> Tuple[List[Row], Dict]:
    from repro.configs.ndp_sim import PRESETS, ndp_machine
    from repro.sim import simulate_batch
    from repro.workloads import generate_trace

    preset = PRESETS["smoke" if fast else "full"]
    mach = ndp_machine(cores)
    rows: List[Row] = []
    summary: Dict = {"preset": preset.name, "cores": cores, "pairs": {}}
    for name, fixture, workload in pairs:
        path = (fixture if os.path.isabs(fixture)
                else os.path.join(_ROOT, FIXTURE_DIR, fixture))
        t0 = time.perf_counter()
        synth = generate_trace(workload, cores, preset=preset)
        # real and synthetic share the machine shape: one 2-lane dispatch
        real_res, synth_res = simulate_batch(
            mach, [f"trace:{path}", synth], length=preset.trace_len,
            chunk=preset.chunk)
        wall = time.perf_counter() - t0
        real_m, synth_m = _metrics(real_res), _metrics(synth_res)
        checks = {
            "real_translation_intensive":
                real_m["tlb_miss_rate"] >= 0.10,
            "synthetic_translation_intensive":
                synth_m["tlb_miss_rate"] >= 0.10,
            "ndpage_wins_on_real_trace":
                real_m["ndpage_speedup"] >= 1.0,
        }
        for metric in ("tlb_miss_rate", "pte_l1_miss_rate",
                       "radix_ptw_cyc", "ndpage_speedup"):
            rows.append((
                f"trace_validate_{name}_{metric}", 0.0,
                f"real={real_m[metric]} synth={synth_m[metric]} "
                f"({workload})"))
        ok = all(checks.values())
        rows.append((f"trace_validate_{name}_check", wall * 1e6,
                     f"{'OK' if ok else 'FAIL'} {checks}"))
        summary["pairs"][name] = {
            "fixture": os.path.relpath(path, _ROOT),
            "workload": workload,
            "real": real_m,
            "synthetic": synth_m,
            "checks": checks,
            "wall_s": round(wall, 2),
        }
    return rows, summary


def merge_into_bench_json(summary: Dict, path: str) -> None:
    """Attach the real-trace table to BENCH_sim.json without clobbering
    the figure-suite / sweeps sections already there."""
    data: Dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"# WARNING: could not read existing {path} ({e}); "
                  "rewriting it with the real_traces section only",
                  file=sys.stderr)
    data["real_traces"] = summary
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def failed_checks(summary: Dict) -> List[str]:
    """Pair names whose structural checks failed — shared by this CLI
    and run.py --trace-validate so both exit nonzero."""
    return [n for n, s in summary["pairs"].items()
            if not all(s["checks"].values())]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--fast", action="store_true",
                   help="smoke-preset windows (CI wall clock)")
    p.add_argument("--cores", type=int, default=2)
    args = p.parse_args(argv)
    fast = args.fast or bool(int(os.environ.get("SIM_FIGS_FAST", "0")))

    from benchmarks.run import _setup_host_devices
    from repro.util.compile_cache import enable_compile_cache
    _setup_host_devices()
    enable_compile_cache()

    rows, summary = run_validation(fast=fast, cores=args.cores)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    path = os.path.join(_ROOT, "BENCH_sim.json")
    merge_into_bench_json(summary, path)
    print(f"# wrote real_traces section into {path}")

    failed = failed_checks(summary)
    if failed:
        print(f"# REAL-TRACE CHECK FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

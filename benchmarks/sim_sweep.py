"""Sensitivity-figure driver: the paper's design-space sweeps.

Runs the named sweep presets from ``repro.configs.ndp_sim.SWEEPS``
(PWC sizing, L1-DTLB sizing, L1-bypass ablation, flattened-level
choice, core scaling, memory latency) through the sweep engine — one
batched chunked-scan dispatch per compiled-shape bucket — prints
``name,us_per_call,derived`` CSV rows like the figure benchmarks, and
verifies the paper's sensitivity orderings:

  * NDPage >= radix at every PWC size and every TLB size,
  * bypass-off NDPage degrades toward radix (suite mean; stays >= 1),
  * translation overhead grows with core count.

The ``sweeps`` section written into ``BENCH_sim.json`` (merged into the
existing file when present) records, per preset, the point/bucket
counts, PER-BUCKET COMPILE COUNTS from the runner cache, and wall
clock — the "one compile per shape" property is part of the perf
trajectory future PRs compare against.

Usage:
  python benchmarks/sim_sweep.py [--fast] [--presets pwc_size,...]
``--fast`` (or SIM_FIGS_FAST=1) uses the smoke SimPreset windows; the
default uses the paper-figure ``full`` preset.  Set SIM_DEVICES=N to
shard each bucket's batch axis across N XLA host devices.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Tuple

import numpy as np

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: preset -> ordering checks run on its result (name, fn(result) -> bool)
Row = Tuple[str, float, str]


def _speed(r, sel, mech):
    return r.select(mechs=sel).map(lambda x: x.speedup_vs()[mech])


def _rows_axis_sweep(name: str, r, axis: str) -> Tuple[List[Row], Dict]:
    """Rows + checks for sweeps with a (numeric axis x workload) grid and
    the full DEFAULT_MECHS tuple per point."""
    rows: List[Row] = []
    sp = r.speedup("ndpage")                       # (axis, workload)
    for i, v in enumerate(r.axes[axis]):
        per_wl = " ".join(f"{w}={sp[i, j]:.3f}"
                          for j, w in enumerate(r.axes["workload"]))
        rows.append((f"sweep_{name}_{v}", 0.0,
                     f"ndpage_speedup mean={sp[i].mean():.3f} {per_wl}"))
    ok = bool((sp >= 1.0).all())
    rows.append((f"sweep_{name}_check", 0.0,
                 f"ndpage>=radix at every {axis}: {'OK' if ok else 'FAIL'}"
                 f" (min={sp.min():.3f})"))
    return rows, {"ndpage_ge_radix_everywhere": ok,
                  "min_ndpage_speedup": round(float(sp.min()), 4),
                  "mean_by_" + axis: {
                      str(v): round(float(sp[i].mean()), 4)
                      for i, v in enumerate(r.axes[axis])}}


#: bypass-off may beat bypass-on per workload by at most this much
#: (cache-pollution noise on short smoke traces; the suite MEAN must
#: still order correctly) — the bounded-linear margin is thin by
#: construction, the structural widening is checked under the banked
#: model by benchmarks/sim_memory.py
_BYPASS_WL_TOL = 0.02


def _rows_bypass(name: str, r) -> Tuple[List[Row], Dict]:
    m_on, m_off = r.axes["mechs"]
    on = _speed(r, m_on, "ndpage")
    off = _speed(r, m_off, "ndpage_nobyp")
    rows = [(f"sweep_{name}_{w}", 0.0,
             f"bypass_on={on[j]:.3f} bypass_off={off[j]:.3f}")
            for j, w in enumerate(r.axes["workload"])]
    ok = (bool(off.mean() < on.mean()) and bool((off >= 1.0).all())
          and bool((off <= on + _BYPASS_WL_TOL).all()))
    rows.append((f"sweep_{name}_check", 0.0,
                 f"bypass-off degrades toward radix (mean "
                 f"{on.mean():.3f}->{off.mean():.3f}, stays >=1, "
                 f"per-workload within tol): {'OK' if ok else 'FAIL'}"))
    return rows, {"bypass_off_degrades": ok,
                  "mean_on": round(float(on.mean()), 4),
                  "mean_off": round(float(off.mean()), 4),
                  "max_wl_inversion": round(float((off - on).max()), 4)}


def _rows_flatten(name: str, r) -> Tuple[List[Row], Dict]:
    m_pl2, m_pl3 = r.axes["mechs"]
    pl2 = _speed(r, m_pl2, "ndpage")
    pl3 = _speed(r, m_pl3, "ndpage_pl3")
    rows = [(f"sweep_{name}_{w}", 0.0,
             f"pl2={pl2[j]:.3f} pl3={pl3[j]:.3f}")
            for j, w in enumerate(r.axes["workload"])]
    ok = bool((pl2 >= 1).all() and (pl3 >= 1).all())
    rows.append((f"sweep_{name}_check", 0.0,
                 f"both flattenings beat radix: {'OK' if ok else 'FAIL'}"))
    return rows, {"both_flattenings_beat_radix": ok,
                  "mean_pl2": round(float(pl2.mean()), 4),
                  "mean_pl3": round(float(pl3.mean()), 4)}


def _rows_cores(name: str, r) -> Tuple[List[Row], Dict]:
    rows: List[Row] = []
    ptw = r.scalar("avg_ptw_latency", "radix").mean(axis=1)   # (cores,)
    sp = r.speedup("ndpage").mean(axis=1)
    hp = r.map(lambda x: x.speedup_vs()["hugepage"]).mean(axis=1)
    for i, c in enumerate(r.axes["cores"]):
        rows.append((f"sweep_{name}_{c}c", 0.0,
                     f"radix_ptw={ptw[i]:.1f}cyc "
                     f"ndpage_speedup={sp[i]:.3f} "
                     f"hugepage_speedup={hp[i]:.3f}"))
    # Fig 6: walk latency grows with cores (queueing); Fig 12 vs 14:
    # huge pages win at 1 core, collapse below radix by 8 (fragmentation)
    ok = bool((np.diff(ptw) > 0).all()) and bool(hp[0] > 1.0 > hp[-1])
    rows.append((f"sweep_{name}_check", 0.0,
                 "ptw grows with cores + hugepage collapse by 8c: "
                 f"{'OK' if ok else 'FAIL'}"))
    return rows, {"scaling_effects": ok,
                  "radix_ptw_by_cores": {
                      str(c): round(float(ptw[i]), 1)
                      for i, c in enumerate(r.axes["cores"])}}


def _rows_zoo(name: str, r) -> Tuple[List[Row], Dict]:
    """Related-work zoo on the zoo machine: per-workload speedups for
    every mechanism, plus ordering checks loose enough to survive trace
    regeneration but tight enough to catch a broken model (ideal is an
    upper bound; Victima's serial ctlb probe costs bounded overhead)."""
    mech_names = [m for m in r.results.flat[0].mechs if m != "radix"]
    sp = {m: r.map(lambda x, m=m: x.speedup_vs()[m]) for m in mech_names}
    rows: List[Row] = []
    for j, w in enumerate(r.axes["workload"]):
        per = " ".join(f"{m}={sp[m][..., j].mean():.3f}"
                       for m in mech_names)
        rows.append((f"sweep_{name}_{w}", 0.0, per))
    ideal = sp["ideal"]
    ok_ideal = bool(all((ideal >= sp[m] - 1e-6).all()
                        for m in mech_names))
    ok_victima = bool((sp["victima"] >= 0.9).all()) \
        if "victima" in sp else True
    checks = {"ideal_is_upper_bound": ok_ideal,
              "victima_probe_overhead_bounded": ok_victima}
    if "ndpage_search" in sp:
        checks["ndpage_search_beats_radix"] = \
            bool((sp["ndpage_search"] >= 1.0).all())
    rows.append((f"sweep_{name}_check", 0.0,
                 f"ideal upper bound + bounded victima overhead: "
                 f"{'OK' if all(v for v in checks.values()) else 'FAIL'}"))
    return rows, checks


def _rows_victima_reach(name: str, r) -> Tuple[List[Row], Dict]:
    """ctlb_kb reach sensitivity: victima must stay within
    [0.9, ideal] at every reach — the probe overhead is bounded and the
    cache-as-TLB can't beat perfect translation.  NO monotonicity check:
    set-associative LRU reach is not monotone on every trace."""
    v = r.map(lambda x: x.speedup_vs()["victima"])    # (ctlb_kb, wl)
    ideal = r.map(lambda x: x.speedup_vs()["ideal"])
    rows = [(f"sweep_{name}_{kb}kb", 0.0,
             "victima " + " ".join(
                 f"{w}={v[i, j]:.3f}"
                 for j, w in enumerate(r.axes["workload"])))
            for i, kb in enumerate(r.axes["ctlb_kb"])]
    ok = bool((v >= 0.9).all()) and bool((v <= ideal + 1e-6).all())
    rows.append((f"sweep_{name}_check", 0.0,
                 f"victima within [0.9, ideal] at every reach: "
                 f"{'OK' if ok else 'FAIL'} (min={v.min():.3f})"))
    return rows, {"victima_bounded_everywhere": ok,
                  "mean_by_ctlb_kb": {
                      str(kb): round(float(v[i].mean()), 4)
                      for i, kb in enumerate(r.axes["ctlb_kb"])}}


def _rows_banked(name: str, r) -> Tuple[List[Row], Dict]:
    """Banked-DRAM timing sensitivity: every point runs the banked
    memory model (memory_model x t_cas x t_rp x workload grid, one
    shape, one compile).  Checks: NDPage still beats radix at every
    timing point, and total cycles are monotone non-decreasing in
    ``t_cas`` (every DRAM access pays the column read, so a slower CAS
    can never speed the machine up)."""
    sp = r.speedup("ndpage")       # (model, t_cas, t_rp, workload)
    cyc = r.map(lambda x: float(x.cycles.mean()))
    t_cas = r.axes["memory.t_cas"]
    rows = [(f"sweep_{name}_tcas{v}", 0.0,
             f"ndpage_speedup mean={sp[:, i].mean():.3f} "
             f"cycles mean={cyc[:, i].mean():.0f}")
            for i, v in enumerate(t_cas)]
    ok_sp = bool((sp >= 1.0).all())
    ok_mono = bool((np.diff(cyc, axis=1) >= -1e-6).all())
    ok = ok_sp and ok_mono
    rows.append((f"sweep_{name}_check", 0.0,
                 f"ndpage>=radix everywhere + cycles monotone in t_cas: "
                 f"{'OK' if ok else 'FAIL'} (min={sp.min():.3f})"))
    return rows, {"ndpage_ge_radix_everywhere": ok_sp,
                  "cycles_monotone_in_t_cas": ok_mono,
                  "min_ndpage_speedup": round(float(sp.min()), 4),
                  "mean_by_t_cas": {
                      str(v): round(float(sp[:, i].mean()), 4)
                      for i, v in enumerate(t_cas)}}


_HANDLERS = {
    "pwc_size": lambda n, r: _rows_axis_sweep(n, r, "pwc_entries"),
    "tlb_size": lambda n, r: _rows_axis_sweep(n, r, "l1_dtlb.entries"),
    "mem_latency": lambda n, r: _rows_axis_sweep(n, r, "memory.latency"),
    "banked_timing": _rows_banked,
    "l1_bypass": _rows_bypass,
    "flatten_level": _rows_flatten,
    "core_scaling": _rows_cores,
    "zoo": _rows_zoo,
    "victima_reach": _rows_victima_reach,
}


def run_sweeps(presets: List[str], fast: bool) -> Tuple[List[Row], Dict]:
    from repro.configs.ndp_sim import PRESETS
    from repro.sim import sweep

    sim_preset = PRESETS["smoke" if fast else "full"]
    rows: List[Row] = []
    summary: Dict = {"preset": sim_preset.name, "sweeps": {}}
    for name in presets:
        t0 = time.perf_counter()
        r = sweep(name, preset=sim_preset.name)
        wall = time.perf_counter() - t0
        handler = _HANDLERS.get(name)
        checks: Dict = {}
        if handler is not None:
            srows, checks = handler(name, r)
            rows.extend(srows)
        rows.append((f"sweep_{name}_engine", wall * 1e6 / r.stats["points"],
                     f"{r.stats['points']}pts {r.stats['buckets']}buckets "
                     f"{r.stats['runner_compiles']}compiles "
                     f"{wall:.1f}s"))
        summary["sweeps"][name] = {
            "points": r.stats["points"],
            "buckets": r.stats["buckets"],
            "runner_compiles": r.stats["runner_compiles"],
            "compiles_per_bucket": [b["compiles"]
                                    for b in r.stats["per_bucket"]],
            "bucket_lanes": [b["lanes"] for b in r.stats["per_bucket"]],
            "wall_s": round(wall, 2),
            "checks": checks,
        }
    return rows, summary


def merge_into_bench_json(summary: Dict, path: str) -> None:
    """Attach the sweep summary to BENCH_sim.json without clobbering the
    figure-suite perf numbers already there."""
    data: Dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            # keep going (the sweep data is still worth writing) but
            # say so: the figure-suite perf section is being lost
            print(f"# WARNING: could not read existing {path} ({e}); "
                  "rewriting it with the sweeps section only",
                  file=sys.stderr)
    data["sweeps"] = summary["sweeps"]
    data["sweeps_preset"] = summary["preset"]
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--fast", action="store_true",
                   help="smoke-preset windows (CI wall clock)")
    p.add_argument("--presets", default=",".join(_HANDLERS),
                   help="comma-separated preset names (default: all)")
    args = p.parse_args(argv)
    fast = args.fast or bool(int(os.environ.get("SIM_FIGS_FAST", "0")))

    # same env plumbing as run.py: host-device sharding + XLA cache
    from benchmarks.run import _setup_host_devices
    from repro.util.compile_cache import enable_compile_cache
    _setup_host_devices()
    enable_compile_cache()

    presets = [s for s in args.presets.split(",") if s]
    rows, summary = run_sweeps(presets, fast)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    path = os.path.join(_ROOT, "BENCH_sim.json")
    merge_into_bench_json(summary, path)
    print(f"# wrote sweeps section into {path}")

    failed = failed_checks(summary)
    if failed:
        print(f"# ORDERING CHECK FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


def failed_checks(summary: Dict) -> List[str]:
    """Preset names whose ordering checks (the boolean entries) failed —
    shared by this CLI and run.py --sweeps so both exit nonzero."""
    return [n for n, s in summary["sweeps"].items()
            if not all(v for v in s["checks"].values()
                       if isinstance(v, bool))]


if __name__ == "__main__":
    sys.exit(main())

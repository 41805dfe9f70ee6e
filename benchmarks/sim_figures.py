"""Reproduction benchmarks: one function per paper figure (Figs 4-8, 12-14).

Each returns a list of CSV rows (name, us_per_call, derived) where
``derived`` carries the figure's metric; a JSON blob with the full data is
written to bench_results.json for EXPERIMENTS.md.

Two paths share all the code:

* default: the paper-figure configuration (8000-entry windows),
* fast (``SIM_FIGS_FAST=1`` or ``benchmarks/run.py --fast``): the
  ``smoke`` preset's short windows — same engine, same orderings, CI
  wall-clock.

``run_all`` groups the 66 (workload, machine, cores) simulations into
**batch buckets** — one per (machine, cores), all workloads stacked on
the engine's B axis — and runs each bucket as a single
``simulate_batch`` dispatch (sharded across host devices when
``SIM_DEVICES`` is set).  Per-stage wall clock (trace generation,
estimated compile, steady-state run) is accumulated for BENCH_sim.json.
"""
from __future__ import annotations

import os
import time
from typing import Dict, List, Tuple

import numpy as np

from repro.configs.ndp_sim import (CORE_COUNTS, PRESETS, WORKLOADS,
                                   cpu_machine, ndp_machine)
from repro.core import page_table as PT
from repro.sim import simulate_batch
from repro.sim.mechanisms import DEFAULT_MECHS
from repro.workloads import generate_trace, generate_traces

FAST = bool(int(os.environ.get("SIM_FIGS_FAST", "0")))
PRESET = PRESETS["smoke" if FAST else "full"]
TRACE_LEN = PRESET.trace_len

#: (workload, machine, cores) -> (SimResult, per-sim wall seconds)
_CACHE: Dict[Tuple[str, str, int], Tuple[object, float]] = {}
#: accumulated per-stage wall clock across every bucket run
_STAGES = {"trace_gen_s": 0.0, "compile_s": 0.0, "run_s": 0.0}


def _machine(machine: str, cores: int):
    return ndp_machine(cores) if machine == "ndp" else cpu_machine(cores)


def _run_bucket(machine: str, cores: int) -> None:
    """One (machine, cores) bucket: every workload batched on the B axis
    through a single chunked-scan dispatch.  Memoized like _sim: a
    bucket already in _CACHE is not re-simulated (repeated run_all()
    calls in one process must not double-count _STAGES)."""
    workloads = list(WORKLOADS)
    if all((w, machine, cores) in _CACHE for w in workloads):
        return
    t0 = time.perf_counter()
    traces = generate_traces(workloads, cores, preset=PRESET)
    _STAGES["trace_gen_s"] += time.perf_counter() - t0

    tm: Dict = {}
    t0 = time.perf_counter()
    results = simulate_batch(_machine(machine, cores), traces,
                             chunk=PRESET.chunk, timings=tm)
    wall = time.perf_counter() - t0
    # stages are disjoint: run_s excludes the ahead-of-time compile
    _STAGES["compile_s"] += tm["compile_s"]
    _STAGES["run_s"] += tm["run_s"]

    per_sim = wall / len(workloads)
    for w, res in zip(workloads, results):
        _CACHE[(w, machine, cores)] = (res, per_sim)


def _sim(workload: str, machine: str, cores: int):
    key = (workload, machine, cores)
    if key not in _CACHE:
        _run_bucket(machine, cores)      # fills every workload of the bucket
    return _CACHE[key]


def _all_combos() -> List[Tuple[str, str, int]]:
    combos = []
    for w in WORKLOADS:
        for cores in CORE_COUNTS:
            combos.append((w, "ndp", cores))
            combos.append((w, "cpu", cores))
    return combos


def _all_buckets() -> List[Tuple[str, int]]:
    """The batch grouping of :func:`_all_combos`: one bucket per
    (machine, cores), workloads riding the B axis."""
    return [(machine, cores) for cores in CORE_COUNTS
            for machine in ("ndp", "cpu")]


def prewarm() -> float:
    """Run every simulation the figures need, one batched dispatch per
    bucket.  Returns the wall-clock spent."""
    t0 = time.time()
    for machine, cores in _all_buckets():
        _run_bucket(machine, cores)
    return time.time() - t0


def fig4_ptw_latency() -> List[Tuple[str, float, str]]:
    """Avg PTW latency, 4-core NDP vs CPU (paper: 474.56 vs ~144, +229%)."""
    rows = []
    nd_all, cpu_all = [], []
    for w in WORKLOADS:
        nd, t1 = _sim(w, "ndp", 4)
        cp, t2 = _sim(w, "cpu", 4)
        nd_ptw = nd.scalar("avg_ptw_latency", "radix")
        cp_ptw = cp.scalar("avg_ptw_latency", "radix")
        nd_all.append(nd_ptw)
        cpu_all.append(cp_ptw)
        rows.append((f"fig4_ptw_{w}", (t1 + t2) * 1e6,
                     f"ndp={nd_ptw:.1f}cyc cpu={cp_ptw:.1f}cyc"))
    inc = (np.mean(nd_all) / np.mean(cpu_all) - 1) * 100
    rows.append(("fig4_ptw_avg", 0.0,
                 f"ndp={np.mean(nd_all):.1f} cpu={np.mean(cpu_all):.1f} "
                 f"increment={inc:.0f}% (paper: 474.56 / +229%)"))
    return rows


def fig5_translation_overhead() -> List[Tuple[str, float, str]]:
    """Fraction of execution spent translating, 4 cores (paper: 67.1% NDP
    vs 34.51% CPU)."""
    rows = []
    nd_all, cpu_all = [], []
    for w in WORKLOADS:
        nd, t1 = _sim(w, "ndp", 4)
        cp, t2 = _sim(w, "cpu", 4)
        ndf = nd.scalar("translation_fraction", "radix")
        cpf = cp.scalar("translation_fraction", "radix")
        nd_all.append(ndf)
        cpu_all.append(cpf)
        rows.append((f"fig5_overhead_{w}", (t1 + t2) * 1e6,
                     f"ndp={ndf:.3f} cpu={cpf:.3f}"))
    rows.append(("fig5_overhead_avg", 0.0,
                 f"ndp={np.mean(nd_all):.3f} cpu={np.mean(cpu_all):.3f} "
                 "(paper: 0.671 / 0.345)"))
    return rows


def fig6_core_scaling() -> List[Tuple[str, float, str]]:
    """PTW latency + overhead vs core count (paper NDP: 242.85 -> 551.83)."""
    rows = []
    for cores in CORE_COUNTS:
        for machine in ("ndp", "cpu"):
            ptws, tfs, us = [], [], 0.0
            for w in WORKLOADS:
                r, t = _sim(w, machine, cores)
                ptws.append(r.scalar("avg_ptw_latency", "radix"))
                tfs.append(r.scalar("translation_fraction", "radix"))
                us += t * 1e6
            rows.append((f"fig6_{machine}_{cores}c", us,
                         f"ptw={np.mean(ptws):.1f} "
                         f"overhead={np.mean(tfs):.3f}"))
    return rows


def fig7_miss_rates() -> List[Tuple[str, float, str]]:
    """L1 miss of PTEs vs data (radix) vs ideal data (paper: 98.28% PTE;
    35.89% vs 26.16% data)."""
    rows = []
    pte, dat, ideal = [], [], []
    for w in WORKLOADS:
        r, t = _sim(w, "ndp", 4)
        pte.append(r.scalar("pte_l1_miss_rate", "radix"))
        dat.append(r.scalar("data_l1_miss_rate", "radix"))
        ideal.append(r.scalar("data_l1_miss_rate", "ideal"))
        rows.append((f"fig7_miss_{w}", t * 1e6,
                     f"pte={pte[-1]:.3f} data={dat[-1]:.3f} "
                     f"ideal={ideal[-1]:.3f}"))
    rows.append(("fig7_miss_avg", 0.0,
                 f"pte={np.mean(pte):.3f} data={np.mean(dat):.3f} "
                 f"ideal={np.mean(ideal):.3f} "
                 "(paper: .983 / .359 / .262)"))
    return rows


def fig8_occupancy() -> List[Tuple[str, float, str]]:
    """Page-table occupancy per level (paper: PL2 98.24%, PL1 97.97%)."""
    rows = []
    occs = []
    for w in WORKLOADS:
        t0 = time.time()
        tr = generate_trace(w, 4, preset=PRESET)
        # occupancy over the dataset's allocated footprint: data-intensive
        # kernels touch essentially all resident pages over the full run;
        # the touched-VPN set of the window under-samples, so evaluate on
        # the footprint range (what the OS has mapped).
        vpns = np.arange(0, tr["pages"], dtype=np.int64)
        l4, l3, l2, l1 = PT.occupancy_by_level(vpns)
        occs.append((l4, l3, l2, l1))
        rows.append((f"fig8_occ_{w}", (time.time() - t0) * 1e6,
                     f"PL4={l4:.4f} PL3={l3:.4f} PL2={l2:.3f} PL1={l1:.3f}"))
    m = np.mean(occs, axis=0)
    rows.append(("fig8_occ_avg", 0.0,
                 f"PL4={m[0]:.4f} PL3={m[1]:.4f} PL2={m[2]:.3f} "
                 f"PL1={m[3]:.3f} (paper: .0043/.0312/.9824/.9797)"))
    return rows


def _speedup_fig(cores: int, fig: str, paper: Dict[str, float]):
    rows = []
    sp = {m: [] for m in DEFAULT_MECHS if m != "radix"}
    for w in WORKLOADS:
        r, t = _sim(w, "ndp", cores)
        s = r.speedup_vs()
        for m in sp:
            sp[m].append(s[m])
        rows.append((f"{fig}_{w}", t * 1e6,
                     " ".join(f"{m}={s[m]:.3f}" for m in sp)))
    avg = {m: float(np.mean(v)) for m, v in sp.items()}
    rows.append((f"{fig}_avg", 0.0,
                 " ".join(f"{m}={avg[m]:.3f}" for m in sp)
                 + f" (paper: {paper})"))
    return rows, avg


def fig12_single_core():
    return _speedup_fig(1, "fig12_1c",
                        {"ech": 1.176, "hugepage": 1.08, "ndpage": 1.344})


def fig13_four_core():
    return _speedup_fig(4, "fig13_4c",
                        {"ech": 1.299, "ndpage": 1.426})


def fig14_eight_core():
    return _speedup_fig(8, "fig14_8c",
                        {"ech": 1.078, "hugepage": 0.901, "ndpage": 1.407})


ALL_FIGS = [fig4_ptw_latency, fig5_translation_overhead, fig6_core_scaling,
            fig7_miss_rates, fig8_occupancy]


def perf_summary() -> Dict:
    """Per-mechanism cycles + engine wall-clock for BENCH_sim.json —
    the perf trajectory future PRs compare against.  ``stages`` breaks
    the fleet wall into trace generation / ahead-of-time compile /
    steady run."""
    mech_cycles: Dict[str, List[float]] = {m: [] for m in DEFAULT_MECHS}
    walls = []
    steps = 0
    for (w, machine, cores), (res, wall) in sorted(_CACHE.items()):
        walls.append(wall)
        steps += res.accesses * cores
        if machine == "ndp" and cores == 4:
            for i, m in enumerate(res.mechs):
                mech_cycles[m].append(float(res.cycles.mean(axis=1)[i]))
    total = float(np.sum(walls))
    return {
        "preset": PRESET.name,
        "trace_len": TRACE_LEN,
        "num_sims": len(walls),
        "num_batches": len(_all_buckets()),
        "sim_wall_s_total": round(total, 3),
        "sim_wall_s_mean": round(float(np.mean(walls)), 4) if walls else 0.0,
        "steps_per_sec": round(steps / total, 1) if total else 0.0,
        # compile-free throughput: the regression gate compares this one
        # (a .jax_cache miss must not read as an engine slowdown)
        "steps_per_sec_steady": (round(steps / _STAGES["run_s"], 1)
                                 if _STAGES["run_s"] else 0.0),
        "stages": {k: round(v, 3) for k, v in _STAGES.items()},
        "mechanisms": {
            m: {"mean_cycles_ndp4": round(float(np.mean(v)), 1),
                "speedup_vs_radix": round(
                    float(np.mean(mech_cycles["radix"]) / np.mean(v)), 4)}
            for m, v in mech_cycles.items() if v
        },
    }


def run_all() -> Tuple[List[Tuple[str, float, str]], Dict]:
    rows: List[Tuple[str, float, str]] = []
    summary: Dict = {}
    warm_s = prewarm()
    rows.append(("prewarm_all_sims", warm_s * 1e6,
                 f"{len(_CACHE)} sims in {len(_all_buckets())} batches, "
                 f"{PRESET.name} preset"))
    for fn in ALL_FIGS:
        rows.extend(fn())
    for fn, paper_nd in ((fig12_single_core, 1.344), (fig13_four_core, 1.426),
                         (fig14_eight_core, 1.407)):
        r, avg = fn()
        rows.extend(r)
        summary[fn.__name__] = {"ours": avg, "paper_ndpage": paper_nd}
    summary["perf"] = perf_summary()
    return rows, summary


if __name__ == "__main__":
    import json
    rows, summary = run_all()
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    print(json.dumps(summary, indent=1))

"""Mechanism-zoo benchmark: four related-work translation designs
through the full pipeline, judged against the searched NDPage point.

The zoo (all registered in :mod:`repro.sim.mechanisms`, all riding the
SAME batched engine — mechanism identity is value-only, so the whole
comparison is ONE compile):

  * ``victima``      — Victima-style cache-as-TLB: a large second TLB
    level carved out of L2-cache capacity (``ctlb_kb``), probed
    serially after an L2-TLB miss; a hit short-circuits the radix walk.
  * ``picorel``      — Picorel/NMP-style inverted-hash translation with
    a direct-segment fast path: one hashed PTE access, no radix levels,
    segment-resident pages skip translation entirely.
  * ``coda``         — CODA-style co-location-aware mapping: walks and
    data of co-located pages land in the LOCAL stack, cutting the
    multi-stack hop penalty to a 10% residual.
  * ``range_table``  — range/segment-table translation: binary-search
    over contiguous-run descriptors, log2(ranges) lookup scaling.

Four phases, each a section of the ``"zoo"`` payload merged into
``BENCH_sim.json`` (never clobbering the figures/sweeps/serving/search
sections):

  * ``sim``      — full-zoo speedup table over the six synthetic
    workloads PLUS the two committed real-trace fixtures, one
    ``simulate_batch`` dispatch (compile count == bucket count == 1
    asserted via the runner cache).
  * ``serving``  — translation-costed paged-KV serving with the zoo
    cost table (segment/inverted orgs price their own PTE-line counts).
  * ``search``   — the ``"zoo"`` design space: mechanism choice as a
    genome knob, searched jointly with ctlb/PWC sizing.
  * ``collisions`` — Picorel's open-addressed inverted table on the
    fixture footprints: load factor vs probe count.

The ``verdict`` section states explicitly where each design beats or
loses to ``ndpage_search`` and why.  Structural checks (ideal is the
upper bound everywhere, Victima's serial-probe overhead is bounded,
Picorel beats the radix baseline, serving completes under every
mechanism) fail the run.

Usage:
  python benchmarks/sim_zoo.py [--fast]
  python benchmarks/run.py --zoo            # same, as a stage
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

Row = Tuple[str, float, str]

#: every mechanism in the comparison, paper set + zoo, one M axis
ZOO_SIM_MECHS = ("radix", "ech", "hugepage", "ndpage", "ndpage_search",
                 "victima", "picorel", "coda", "range_table", "ideal")
#: the serving cost table's mechs (serving reports ndpage vs the zoo)
ZOO_SERVE_MECHS = ("radix", "ndpage", "ndpage_search", "victima",
                   "picorel", "coda", "range_table", "ideal")
#: the reference point every zoo design is judged against
REFERENCE = "ndpage_search"


def _zoo_workloads() -> Tuple[str, ...]:
    from repro.configs.ndp_sim import SEARCH_FIXTURES, SWEEP_WORKLOADS
    return SWEEP_WORKLOADS + SEARCH_FIXTURES


def _wl_label(wl: str) -> str:
    if wl.startswith("trace:"):
        base = os.path.basename(wl[len("trace:"):].partition("?")[0])
        return base.split(".")[0]
    return wl


def run_zoo_sim(fast: bool) -> Tuple[List[Row], Dict]:
    """Phase 1: the full zoo on the zoo machine over synthetics + real
    fixtures — ONE batched dispatch, ONE compile."""
    from repro.configs.ndp_sim import PRESETS, zoo_machine
    from repro.sim.simulator import (runner_cache_info, simulate_batch)

    preset = PRESETS["smoke" if fast else "full"]
    mach = zoo_machine(4)
    wls = _zoo_workloads()
    from repro.workloads import generate_trace
    traces = [wl if wl.startswith("trace:")
              else generate_trace(wl, mach.num_cores, preset=preset)
              for wl in wls]

    info0 = runner_cache_info()
    t0 = time.perf_counter()
    results = simulate_batch(mach, traces, mechs=ZOO_SIM_MECHS,
                             chunk=preset.chunk)
    wall = time.perf_counter() - t0
    compiles = runner_cache_info().misses - info0.misses

    rows: List[Row] = []
    speedups: Dict[str, Dict[str, float]] = {}
    for wl, res in zip(wls, results):
        sp = res.speedup_vs()
        label = _wl_label(wl)
        speedups[label] = {m: round(float(sp[m]), 4)
                           for m in ZOO_SIM_MECHS}
        rows.append((f"zoo_sim_{label}", 0.0,
                     " ".join(f"{m}={sp[m]:.3f}"
                              for m in ZOO_SIM_MECHS if m != "radix")))

    arr = {m: np.array([speedups[_wl_label(w)][m] for w in wls])
           for m in ZOO_SIM_MECHS}
    checks = {
        # ONE shape x ONE walk-fn tuple => one bucket; a warm
        # persistent cache can only lower the count
        "one_compile_one_bucket": compiles <= 1,
        "ideal_upper_bound": bool(all(
            (arr["ideal"] >= arr[m] - 1e-6).all()
            for m in ZOO_SIM_MECHS)),
        "victima_probe_overhead_bounded":
            bool((arr["victima"] >= 0.9).all()),
        "picorel_beats_radix": bool((arr["picorel"] >= 1.0).all()),
        "fixtures_covered": len(wls) == len(results),
    }
    rows.append(("zoo_sim_engine", wall * 1e6 / len(wls),
                 f"{len(wls)}workloads 1bucket {compiles}compiles "
                 f"{wall:.1f}s"))
    section = {"machine": mach.name, "preset": preset.name,
               "mechs": list(ZOO_SIM_MECHS),
               "workloads": [_wl_label(w) for w in wls],
               "speedup_vs_radix": speedups,
               "runner_compiles": compiles, "buckets": 1,
               "wall_s": round(wall, 2), "checks": checks}
    return rows, section


def run_zoo_serving(fast: bool, seed: int = 0) -> Tuple[List[Row], Dict]:
    """Phase 2: translation-costed serving with the zoo cost table —
    the segment/inverted organizations price their own PTE-line
    accounting in the metered decode loop."""
    from benchmarks.serving_translation import SMOKE_MIXES, _engine_factory
    from repro.configs.ndp_sim import zoo_machine
    from repro.serving import Request, ServeEngine
    from repro.sim.cost_model import TranslationCostModel
    from repro.sim.simulator import runner_cache_info

    info0 = runner_cache_info()
    model = TranslationCostModel.from_sim(zoo_machine(4),
                                          mechs=ZOO_SERVE_MECHS)
    cost_compiles = runner_cache_info().misses - info0.misses

    cfg, params = _engine_factory()
    mix = SMOKE_MIXES["decode_heavy"]
    rng = np.random.default_rng(seed)
    eng = ServeEngine(cfg, params, max_batch=4, max_len=96,
                      page_size=8, cost_model=model)
    t0 = time.perf_counter()
    for i in range(mix["n_requests"]):
        lo, hi = mix["prompt"]
        prompt = rng.integers(1, cfg.vocab_size,
                              rng.integers(lo, hi)).astype(np.int32)
        eng.submit(Request(req_id=i, prompt=prompt,
                           max_new_tokens=mix["new_tokens"]))
    done = eng.run()
    wall = time.perf_counter() - t0
    rep = eng.throughput()
    tps = rep["tokens_per_sec"]

    rows: List[Row] = [
        (f"zoo_serving_{m}", 0.0,
         f"{tps[m]:.0f} tok/s "
         f"trans={rep['translation_cycles'][m]:.0f}cyc org="
         f"{model.costs[model.mechs.index(m)].org}")
        for m in model.mechs]
    checks = {
        "ideal_upper_bound": bool(all(tps["ideal"] >= v - 1e-9
                                      for v in tps.values())),
        "all_completed": len(done) == mix["n_requests"],
        "every_mech_priced": set(model.mechs) == set(ZOO_SERVE_MECHS),
    }
    rows.append(("zoo_serving_check", wall * 1e6,
                 f"{'OK' if all(checks.values()) else 'FAIL'} {checks}"))
    section = {
        "machine": model.machine, "mechs": list(model.mechs),
        "orgs": {m: model.costs[model.mechs.index(m)].org
                 for m in model.mechs},
        "cost_model_compiles": cost_compiles,
        "tokens_per_sec": {m: round(v, 1) for m, v in tps.items()},
        "translation_cycles": {
            m: round(v, 1)
            for m, v in rep["translation_cycles"].items()},
        "wall_s": round(wall, 2), "checks": checks,
    }
    return rows, section


def run_zoo_search() -> Tuple[List[Row], Dict]:
    """Phase 3: the ``"zoo"`` design space — mechanism membership is a
    genome knob searched jointly with ctlb/PWC sizing."""
    from repro.sim import search

    result = search("zoo")
    p = result.provenance
    rows: List[Row] = []
    for c in result.frontier:
        o = c.objectives
        rows.append((f"zoo_search_front_{c.mech}", 0.0,
                     f"speedup={o['mean_speedup']:.4f} "
                     f"sram={o['sram_kb']:g}KB "
                     f"worst_ptw={o['worst_ptw']:.1f}cyc"))
    frontier_mechs = sorted({c.mech for c in result.frontier})
    checks = {
        "frontier_nonempty": bool(result.frontier),
        "compile_bound":
            p["runner_compiles"] <= p["distinct_buckets"],
    }
    rows.append(("zoo_search_engine",
                 p["wall_s"] * 1e6 / max(p["evaluated"], 1),
                 f"{p['evaluated']}cands frontier_mechs="
                 f"{','.join(frontier_mechs)} "
                 f"{p['runner_compiles']}compiles {p['wall_s']:.1f}s"))
    section = {
        "space": "zoo", "evaluated": p["evaluated"],
        "runner_compiles": p["runner_compiles"],
        "frontier_mechs": frontier_mechs,
        "frontier": [c.to_json_dict() for c in result.frontier],
        "wall_s": round(p["wall_s"], 2), "checks": checks,
    }
    return rows, section


def run_collisions() -> Tuple[List[Row], Dict]:
    """Phase 4: Picorel's open-addressed inverted table on the real
    fixture footprints — the hash-collision cost its single-access
    latency model abstracts, reported so the abstraction is visible."""
    from repro.configs.ndp_sim import SEARCH_FIXTURES
    from repro.core.page_table import inverted_table_insert
    from repro.workloads import generate_trace

    rows: List[Row] = []
    per_fix: Dict[str, Dict] = {}
    for wl in SEARCH_FIXTURES:
        tr = generate_trace(wl, 4)
        vpns = np.unique(np.asarray(tr["vpn"]))
        # size the table one doubling above the footprint, as a real
        # inverted page table would be provisioned
        log2_slots = max(int(np.ceil(np.log2(max(len(vpns), 2)))) + 1, 4)
        _, probes = inverted_table_insert(vpns, log2_slots=log2_slots)
        label = _wl_label(wl)
        stats = {"footprint_pages": int(len(vpns)),
                 "log2_slots": log2_slots,
                 "load_factor": round(len(vpns) / (1 << log2_slots), 4),
                 "mean_extra_probes": round(float(probes.mean()), 4),
                 "max_extra_probes": int(probes.max()),
                 "collision_rate":
                     round(float((probes > 0).mean()), 4)}
        per_fix[label] = stats
        rows.append((f"zoo_collisions_{label}", 0.0,
                     f"load={stats['load_factor']:.3f} "
                     f"mean_extra_probes="
                     f"{stats['mean_extra_probes']:.3f} "
                     f"collisions={stats['collision_rate']:.1%}"))
    ok = all(s["mean_extra_probes"] < 2.0 for s in per_fix.values())
    checks = {"probe_chains_short_at_half_load": ok}
    rows.append(("zoo_collisions_check", 0.0,
                 f"mean extra probes < 2 at <=50% load: "
                 f"{'OK' if ok else 'FAIL'}"))
    return rows, {"fixtures": per_fix, "checks": checks}


def build_verdict(sim_section: Dict) -> Dict:
    """Where each zoo design beats / loses to ``ndpage_search`` — the
    explicit judgement the comparison exists to produce."""
    from repro.sim.mechanisms import ZOO_MECHS
    sp = sim_section["speedup_vs_radix"]
    wls = sim_section["workloads"]
    out: Dict[str, Dict] = {}
    reasons = {
        "victima": ("serial ctlb probe is pure overhead when the "
                    "workload either fits the L2 TLB or blows past the "
                    "cache-as-TLB reach; wins only in the in-between "
                    "reuse band"),
        "picorel": ("one hashed PTE access beats a 4-level walk "
                    "whenever PWC locality is poor; ignores hash "
                    "collisions (see the collisions section)"),
        "coda": ("co-location only discounts the multi-stack hop "
                 "penalty, a small slice of total walk latency here"),
        "range_table": ("binary-search depth tracks fragmentation: "
                        "competitive on contiguous footprints, pays on "
                        "fragmented ones"),
    }
    for m in ZOO_MECHS:
        wins = [w for w in wls if sp[w][m] > sp[w][REFERENCE] + 1e-4]
        loses = [w for w in wls if sp[w][m] < sp[w][REFERENCE] - 1e-4]
        ratio = float(np.mean([sp[w][m] / sp[w][REFERENCE]
                               for w in wls]))
        out[m] = {
            "beats_ndpage_search_on": wins,
            "loses_to_ndpage_search_on": loses,
            "mean_relative_speedup": round(ratio, 4),
            "verdict": (f"{'beats' if ratio > 1 else 'loses to'} "
                        f"{REFERENCE} on average "
                        f"({ratio:.3f}x): {reasons[m]}"),
        }
    return out


def run_all(fast: bool = True, seed: int = 0
            ) -> Tuple[List[Row], Dict]:
    rows: List[Row] = []
    summary: Dict = {}
    r, summary["sim"] = run_zoo_sim(fast)
    rows += r
    r, summary["serving"] = run_zoo_serving(fast, seed)
    rows += r
    r, summary["search"] = run_zoo_search()
    rows += r
    r, summary["collisions"] = run_collisions()
    rows += r
    summary["verdict"] = build_verdict(summary["sim"])
    for m, v in summary["verdict"].items():
        rows.append((f"zoo_verdict_{m}", 0.0, v["verdict"]))
    return rows, summary


def failed_checks(summary: Dict) -> List[str]:
    """``phase.check`` names of the failed boolean gates — shared by
    this CLI and ``run.py --zoo`` so both exit nonzero."""
    out = []
    for phase, sec in summary.items():
        if not isinstance(sec, dict):
            continue
        for name, v in sec.get("checks", {}).items():
            if isinstance(v, bool) and not v:
                out.append(f"{phase}.{name}")
    return out


def merge_into_bench_json(summary: Dict, path: str) -> None:
    """Attach the zoo section to BENCH_sim.json without clobbering the
    figures/sweeps/real_traces/serving/search sections already there."""
    data: Dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"# WARNING: could not read existing {path} ({e}); "
                  "rewriting it with the zoo section only",
                  file=sys.stderr)
    data["zoo"] = summary
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--fast", action="store_true",
                   help="smoke-preset windows (CI wall clock)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    fast = args.fast or bool(int(os.environ.get("SIM_FIGS_FAST", "0")))

    from benchmarks.run import _setup_host_devices
    from repro.util.compile_cache import enable_compile_cache
    _setup_host_devices()
    enable_compile_cache()

    rows, summary = run_all(fast=fast, seed=args.seed)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    path = os.path.join(_ROOT, "BENCH_sim.json")
    merge_into_bench_json(summary, path)
    print(f"# merged zoo section into {path}")

    failed = failed_checks(summary)
    if failed:
        print(f"# ZOO CHECK FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

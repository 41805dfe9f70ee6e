"""Fleet-scale costed serving benchmark: continuous batching at
thousands of live sequences, prefix sharing, translation-aware
admission.

Four runs of the :class:`repro.serving.FleetEngine` (one jitted
surrogate decode shared by ALL of them — the mechanism, the mix and the
sharing flag never enter the jit, so the whole benchmark compiles ONE
decode graph):

  * ``shared``       — a shared-prompt mix (``prefix_groups`` system
    prompts of ``prefix_len`` tokens + per-request tails) with prefix
    sharing ON: sharers map the fully-covered prefix pages to one
    refcounted allocation and the radix-org pricing dedups the shared
    leaves batch-globally.
  * ``unshared``     — the SAME mix with sharing OFF (the control):
    generated tokens must be bit-identical; only radix-family
    translation cycles may move.  The gap is the radix line-sharing
    win the flat (NDPage) org cannot have — and it shows up in
    tokens/sec.
  * ``independent``  — a no-prefix mix (nothing to share; baseline
    shape of the fleet numbers).
  * ``budget``       — the shared mix under a per-step translation
    cycle budget: admission prices each candidate under the budget
    mechanism and stops admitting when the estimated per-step spend
    would exceed it (plus sustained-overshoot preemption), so peak
    concurrency is set by TRANSLATION cost, not page supply.

The ``shared`` run's accumulated translation cycles are then re-priced
under a ``model_cycles_per_token`` grid (same totals, no re-run) to map
where translation stops mattering for end-to-end tokens/sec.

Structural gates (run fails nonzero): peak concurrency reaches the
fleet target, one decode trace, bit-exact tokens sharing on/off, ndpage
>= radix and ideal the upper bound everywhere, radix (not ndpage) gains
from sharing in cycles AND tokens/sec, the budget run peaks strictly
below the unbudgeted run, the meter's per-request budgets partition its
totals, and the mcpt speedup curve is monotone.

The ``"serving_fleet"`` section lands in ``BENCH_sim.json`` (merged,
never clobbering the other sections).

Usage:
  python benchmarks/serving_fleet.py [--smoke] [--pinned] [--seed N]
  python benchmarks/run.py --serving-fleet       # same, as a stage
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

Row = Tuple[str, float, str]


def _fleet_params(fast: bool) -> Dict:
    from repro.configs.ndp_sim import SERVING_FLEET
    p = {k: v for k, v in SERVING_FLEET.items() if k != "smoke"}
    if fast:
        p.update(SERVING_FLEET["smoke"])
    return p


def _mix(p: Dict, seed: int, shared: bool):
    """The request list for one run — built fresh per run (requests are
    mutated by the scheduler) but identical across runs of the same
    seed/mix, so on/off comparisons are apples-to-apples."""
    import numpy as np

    from repro.serving import Request
    rng = np.random.default_rng(seed)
    reqs = []
    if shared:
        prefixes = {g: rng.integers(1, 30_000, p["prefix_len"])
                    for g in range(p["prefix_groups"])}
        for i in range(p["num_requests"]):
            g = i % p["prefix_groups"]
            tail = rng.integers(1, 30_000, p["tail_tokens"])
            reqs.append(Request.build(
                i, np.concatenate([prefixes[g], tail]),
                max_new_tokens=p["new_tokens"],
                prefix_id=g, prefix_len=p["prefix_len"]))
    else:
        lo, hi = p["independent_prompt"]
        for i in range(p["num_requests"]):
            prompt = rng.integers(1, 30_000, rng.integers(lo, hi))
            reqs.append(Request.build(i, prompt,
                                      max_new_tokens=p["new_tokens"]))
    return reqs


def _run_one(p: Dict, model, reqs, *, prefix_sharing: bool,
             translation_budget=None) -> Tuple[Dict, object]:
    from repro.serving import FleetEngine
    eng = FleetEngine(
        max_batch=p["max_batch"], max_len=p["max_len"],
        page_size=p["page_size"], leaf_size=p["leaf_size"],
        cost_model=model, prefix_sharing=prefix_sharing,
        translation_budget=translation_budget,
        budget_mech=p["budget_mech"])
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    rep = eng.throughput()
    rep["finished"] = done
    rep["wall_s"] = round(wall, 2)
    return rep, eng


def _meter_conserved(eng) -> bool:
    """The meter's per-request budgets must partition its totals (all
    slots released, nothing double- or under-counted)."""
    import numpy as np
    budgets = eng.meter.request_budgets()
    if not budgets:
        return eng.meter.total.sum() == 0.0
    acc = np.sum(list(budgets.values()), axis=0)
    return bool(np.allclose(acc, eng.meter.total, rtol=1e-9, atol=1e-6))


def run_fleet(fast: bool = True, pinned: bool = False, seed: int = 0
              ) -> Tuple[List[Row], Dict]:
    from repro.serving.fleet import decode_trace_count
    from repro.sim.cost_model import TranslationCostModel

    model = TranslationCostModel.for_machine(
        source="pinned" if pinned else "sweep")
    p = _fleet_params(fast)
    traces0 = decode_trace_count()

    runs: Dict[str, Dict] = {}
    engines: Dict[str, object] = {}
    runs["shared"], engines["shared"] = _run_one(
        p, model, _mix(p, seed, shared=True), prefix_sharing=True)
    runs["unshared"], engines["unshared"] = _run_one(
        p, model, _mix(p, seed, shared=True), prefix_sharing=False)
    runs["independent"], engines["independent"] = _run_one(
        p, model, _mix(p, seed + 1, shared=False), prefix_sharing=True)
    runs["budget"], engines["budget"] = _run_one(
        p, model, _mix(p, seed, shared=True), prefix_sharing=True,
        translation_budget=p["translation_budget"])
    trace_delta = decode_trace_count() - traces0

    # -- gates ---------------------------------------------------------------
    n = p["num_requests"]
    fleet_target = min(p["max_batch"], n)
    sh, un, bud = runs["shared"], runs["unshared"], runs["budget"]
    gen = {}
    for name, rep in runs.items():
        gen[name] = {r.req_id: list(r.generated) for r in rep["finished"]}
    tps_sh, tps_un = sh["tokens_per_sec"], un["tokens_per_sec"]
    cyc_sh, cyc_un = sh["translation_cycles"], un["translation_cycles"]
    bs = bud["stats"]

    checks = {
        # fleet scale: the batch actually fills, on one compiled graph
        "fleet_concurrency": all(
            runs[r]["peak_running"] >= fleet_target
            for r in ("shared", "unshared", "independent")),
        "one_decode_trace": trace_delta <= 1,
        "all_completed": all(
            len(gen[r]) == n for r in ("shared", "unshared",
                                       "independent")),
        # sharing is a pure translation-cost effect: tokens identical
        "tokens_exact_on_off": gen["shared"] == gen["unshared"],
        # the paper's ordering, under every run
        "ndpage_ge_radix": all(
            rep["tokens_per_sec"]["ndpage"]
            >= rep["tokens_per_sec"]["radix"] for rep in runs.values()),
        "ideal_upper_bound": all(
            rep["tokens_per_sec"]["ideal"] >= v - 1e-9
            for rep in runs.values()
            for v in rep["tokens_per_sec"].values()),
        # the radix line-sharing win: cycles drop AND tokens/sec move,
        # while the flat org (per-sequence contiguous rows) is immune
        "radix_gains_cycles": cyc_sh["radix"] < cyc_un["radix"],
        "radix_gains_tps": tps_sh["radix"] > tps_un["radix"],
        "flat_immune": cyc_sh["ndpage"] == cyc_un["ndpage"],
        "sharing_gap_radix_over_flat": (
            (tps_sh["radix"] / tps_un["radix"])
            > (tps_sh["ndpage"] / tps_un["ndpage"])),
        # translation-aware admission binds concurrency
        "budget_caps_concurrency":
            bud["peak_running"] < sh["peak_running"],
        "budget_conserves_requests":
            bs["completed"] + bs["shed"] == n,
        # accounting: per-request budgets partition the meter totals
        "meter_conserved": all(_meter_conserved(engines[r])
                               for r in runs),
    }

    # -- mcpt sweep: reprice the SAME totals, no re-run ----------------------
    meter = engines["shared"].meter
    mcpt_rows = []
    for mcpt in p["mcpt_grid"]:
        tps = meter.tokens_per_sec(model_cycles_per_token=mcpt)
        mcpt_rows.append({
            "model_cycles_per_token": mcpt,
            "tokens_per_sec": {m: round(v, 1) for m, v in tps.items()},
            "ndpage_speedup": round(tps["ndpage"] / tps["radix"], 4),
        })
    speedups = [r["ndpage_speedup"] for r in mcpt_rows]
    checks["mcpt_speedup_monotone"] = all(
        a >= b - 1e-9 for a, b in zip(speedups, speedups[1:]))
    checks["translation_matters_at_low_mcpt"] = (
        speedups[0] > speedups[-1])

    # -- report --------------------------------------------------------------
    rows: List[Row] = []
    summary: Dict = {
        "seed": seed,
        "params": {k: (list(v) if isinstance(v, tuple) else v)
                   for k, v in p.items()},
        "cost_model": {"source": model.source, "machine": model.machine,
                       "mechs": list(model.mechs),
                       "model_cycles_per_token":
                           model.model_cycles_per_token},
        "decode_trace_delta": trace_delta,
        "runs": {}, "mcpt_sweep": mcpt_rows, "checks": checks,
    }
    for name, rep in runs.items():
        stats = rep["stats"]
        summary["runs"][name] = {
            "requests": n, "completed": stats["completed"],
            "shed": stats["shed"], "preempted": stats["preempted"],
            "peak_running": rep["peak_running"],
            "steps": rep["steps"], "tokens": rep["tokens"],
            "tcache_hits": rep["tcache_hits"],
            "tcache_misses": rep["tcache_misses"],
            "occupancy_modes": {
                "flat": stats["mode_flat_steps"],
                "radix": stats["mode_radix_steps"]},
            "tokens_per_sec": {m: round(v, 1)
                               for m, v in rep["tokens_per_sec"].items()},
            "translation_cycles": {
                m: round(v, 1)
                for m, v in rep["translation_cycles"].items()},
            "ndpage_speedup": round(rep["tokens_per_sec"]["ndpage"]
                                    / rep["tokens_per_sec"]["radix"], 4),
            "wall_s": rep["wall_s"],
        }
        for m in model.mechs:
            rows.append((f"fleet_{name}_{m}", 0.0,
                         f"{rep['tokens_per_sec'][m]:.0f} tok/s "
                         f"trans={rep['translation_cycles'][m]:.0f}cyc"))
        rows.append((f"fleet_{name}", rep["wall_s"] * 1e6,
                     f"peak={rep['peak_running']} steps={rep['steps']} "
                     f"completed={stats['completed']}/{n}"))
    sharing_gap = tps_sh["radix"] / tps_un["radix"]
    rows.append(("fleet_sharing_gap_radix", 0.0,
                 f"{(sharing_gap - 1) * 100:.2f}% tok/s from prefix "
                 f"sharing (flat: "
                 f"{(tps_sh['ndpage'] / tps_un['ndpage'] - 1) * 100:.2f}%)"))
    ok = all(checks.values())
    rows.append(("fleet_checks", 0.0,
                 f"{'OK' if ok else 'FAIL'} "
                 f"{[k for k, v in checks.items() if not v]}"))
    summary["sharing_gap_radix"] = round(sharing_gap, 4)
    return rows, summary


def merge_into_bench_json(summary: Dict, path: str) -> None:
    """Attach the fleet table to BENCH_sim.json without clobbering the
    other sections already there."""
    data: Dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"# WARNING: could not read existing {path} ({e}); "
                  "rewriting it with the serving_fleet section only",
                  file=sys.stderr)
    data["serving_fleet"] = summary
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def failed_checks(summary: Dict) -> List[str]:
    """Names of failed structural gates — shared by this CLI and
    run.py --serving-fleet so both exit nonzero."""
    return [k for k, v in summary["checks"].items() if not v]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="trimmed fleet (PR fast-lane cost; same "
                        "structure, smaller counts)")
    p.add_argument("--pinned", action="store_true",
                   help="use the committed cost table — no simulator "
                        "run at all (hermetic)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    from benchmarks.run import _setup_host_devices
    from repro.util.compile_cache import enable_compile_cache
    _setup_host_devices()
    enable_compile_cache()

    rows, summary = run_fleet(fast=args.smoke, pinned=args.pinned,
                              seed=args.seed)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    path = os.path.join(_ROOT, "BENCH_sim.json")
    merge_into_bench_json(summary, path)
    print(f"# wrote serving_fleet section into {path}")

    failed = failed_checks(summary)
    if failed:
        print(f"# FLEET CHECK FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

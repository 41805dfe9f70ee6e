"""Design-space search driver + frontier-regression gate.

Runs the seeded evolutionary search from :mod:`repro.sim._search` over a
named space (``repro.configs.ndp_sim.SEARCH_SPACES``), prints
``name,us_per_call,derived`` CSV rows like the other benchmark drivers,
merges the ``"search"`` section into ``BENCH_sim.json`` (never
clobbering the figures/sweeps/real_traces/serving sections), and gates:

  * the Pareto frontier is non-empty and contains no dominated points,
  * the paper's NDPage config was evaluated and carries an explicit
    dominates-paper verdict,
  * compile count stayed within the (machine-shape x walk-fn) bucket
    bound — the sweep engine's no-recompile invariant held,
  * FRONTIER REGRESSION: every genome pinned in
    ``benchmarks/frontier_baseline.json`` is re-evaluated under the
    current engine and must still be non-dominated by anything this
    run discovered.  The pinned genomes are compared on FRESH objective
    values, so the gate is robust to float drift across jax versions
    but fires whenever a model change (or a search improvement) pushes
    a pinned point off the frontier — refresh deliberately with
    ``--update-baseline``.

Usage:
  python benchmarks/sim_search.py [--space default] [--seed N]
                                  [--no-cache] [--update-baseline]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Tuple

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BASELINE_PATH = os.path.join(_ROOT, "benchmarks", "frontier_baseline.json")

Row = Tuple[str, float, str]


def _baseline_genomes(space, baseline: Dict) -> List[Tuple]:
    """The pinned genomes as knob-ordered tuples (JSON lists become the
    tuples the search layer hashes on)."""
    out = []
    for pt in baseline.get("points", []):
        g = pt["genome"]
        out.append(tuple(
            tuple(g[n]) if isinstance(g[n], list) else g[n]
            for n in space.knob_names))
    return out


def check_frontier_baseline(result, path: str = BASELINE_PATH
                            ) -> Tuple[bool, str]:
    """True iff every pinned-frontier genome is still non-dominated.

    Pinned genomes absent from this run's candidate set are re-evaluated
    (one extra bucketed dispatch at most); dominance is then checked
    against everything the run discovered, on current-engine objective
    values.
    """
    from repro.sim._search import (dominates, evaluate_genomes,
                                  genome_key)
    if not os.path.exists(path):
        return True, "no baseline pinned (run --update-baseline)"
    try:
        with open(path) as f:
            baseline = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return False, f"unreadable baseline {path}: {e}"
    if baseline.get("space") != result.space.name:
        return True, (f"baseline pins space {baseline.get('space')!r}, "
                      f"run used {result.space.name!r} — skipped")
    pinned = _baseline_genomes(result.space, baseline)
    if not pinned:
        return False, "baseline has no pinned points"

    # seed the eval cache with everything the run already computed so
    # only genuinely-missing pinned genomes re-dispatch
    cache = {genome_key(result.space, tuple(c.genome.values())): {
        "objectives": c.objectives, "per_workload": c.per_workload,
        "mech": c.mech} for c in result.candidates}
    evals, _ = evaluate_genomes(result.space, pinned, cache=cache)

    field = [c.objectives for c in result.candidates]
    field += [obj for obj, _, _ in evals]
    regressed = []
    for g, (obj, _, _) in zip(pinned, evals):
        if any(dominates(other, obj) for other in field):
            regressed.append(f"{dict(zip(result.space.knob_names, g))} "
                             f"now dominated ({obj})")
    if regressed:
        return False, "; ".join(regressed)
    return True, f"all {len(pinned)} pinned points still non-dominated"


def update_baseline(result, path: str = BASELINE_PATH) -> None:
    """Pin the current frontier's genomes (objectives recorded for
    humans only — the gate always re-evaluates)."""
    data = {
        "space": result.space.name,
        "seed": result.provenance["seed"],
        "objectives": [{"name": n, "direction": d}
                       for n, d in result.objectives],
        "points": [c.to_json_dict() for c in result.frontier],
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=1)
        f.write("\n")


def run_search(space: str = "default", *, seed: int | None = None,
               use_cache: bool = True,
               baseline_path: str = BASELINE_PATH
               ) -> Tuple[List[Row], Dict]:
    """Run the search + all gates.  Returns CSV rows and a summary dict
    whose ``"section"`` is the BENCH_sim.json payload and whose
    ``"checks"`` booleans feed :func:`failed_checks`."""
    from repro.sim._search import pareto_indices
    from repro.sim._search import search as run

    result = run(space, seed=seed, use_cache=use_cache)
    p = result.provenance

    rows: List[Row] = []
    for c in result.frontier:
        o = c.objectives
        rows.append((f"search_front_{c.mech}_{o['sram_kb']:g}KB", 0.0,
                     f"speedup={o['mean_speedup']:.4f} "
                     f"worst_ptw={o['worst_ptw']:.1f}cyc "
                     f"gen={c.gen} origin={c.origin}"))
    v = result.verdict
    rows.append(("search_verdict", 0.0,
                 f"paper config dominated: {v['dominates_paper']} "
                 f"({v['n_dominating']} dominating points)"))
    rows.append(("search_engine",
                 p["wall_s"] * 1e6 / max(p["evaluated"], 1),
                 f"{p['evaluated']}cands {p['lanes_dispatched']}lanes "
                 f"{p['distinct_buckets']}buckets "
                 f"{p['runner_compiles']}compiles {p['wall_s']:.1f}s"))

    refront = pareto_indices([c.objectives for c in result.frontier])
    baseline_ok, baseline_note = check_frontier_baseline(
        result, baseline_path)
    checks = {
        "frontier_nonempty": bool(result.frontier),
        "no_dominated_in_frontier":
            len(refront) == len(result.frontier),
        "paper_evaluated": result.paper.origin == "paper",
        "verdict_present": isinstance(v.get("dominates_paper"), bool),
        # warm persistent caches can only LOWER the compile count
        "compile_bound":
            p["runner_compiles"] <= p["distinct_buckets"],
        "frontier_baseline_ok": baseline_ok,
        "baseline_note": baseline_note,
    }
    rows.append(("search_frontier_gate", 0.0,
                 f"{'OK' if baseline_ok else 'FAIL'}: {baseline_note}"))

    section = result.to_json_dict()
    section["checks"] = checks
    return rows, {"section": section, "checks": checks,
                  "result": result}


def failed_checks(summary: Dict) -> List[str]:
    """Names of the failed boolean gates — shared by this CLI and
    ``run.py --search`` so both exit nonzero."""
    return [n for n, v in summary["checks"].items()
            if isinstance(v, bool) and not v]


def merge_into_bench_json(summary: Dict, path: str) -> None:
    """Attach the search section to BENCH_sim.json without clobbering
    the figures/sweeps/real_traces/serving sections already there."""
    from repro.sim._search import merge_search_section
    merge_search_section(summary["section"], path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--space", default="default",
                    help="search space name (SEARCH_SPACES)")
    ap.add_argument("--seed", type=int, default=None,
                    help="override the space's pinned seed")
    ap.add_argument("--no-cache", action="store_true",
                    help="ignore the on-disk eval cache")
    ap.add_argument("--update-baseline", action="store_true",
                    help="pin the discovered frontier as the new "
                         "regression baseline")
    args = ap.parse_args(argv)

    from benchmarks.run import _setup_host_devices
    from repro.util.compile_cache import enable_compile_cache
    _setup_host_devices()
    enable_compile_cache()

    rows, summary = run_search(args.space, seed=args.seed,
                               use_cache=not args.no_cache)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    path = os.path.join(_ROOT, "BENCH_sim.json")
    merge_into_bench_json(summary, path)
    print(f"# merged search section into {path}")

    if args.update_baseline:
        update_baseline(summary["result"])
        print(f"# pinned frontier baseline -> {BASELINE_PATH}")
        # the just-pinned frontier is non-dominated by construction
        summary["checks"]["frontier_baseline_ok"] = True

    failed = failed_checks(summary)
    if failed:
        print(f"# SEARCH GATE FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

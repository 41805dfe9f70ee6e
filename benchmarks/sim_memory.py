"""Memory-model comparison: bounded_linear vs banked DRAM, end to end.

The banked row-buffer model (``repro.sim.memory_model``) is what prices
the paper's STRUCTURAL claim: a flattened table's leaf span is one
contiguous run of PTE lines, so a walk streams through open DRAM rows,
while radix per-node allocations land on scattered rows and keep paying
precharge+activate.  This driver re-runs the two sensitivity studies
that claim rides on — the L1-bypass ablation and the flattened-level
choice — under BOTH memory models and records whether

  * the bypass margin (ndpage over ndpage_nobyp, suite mean) widens
    when DRAM is banked, and
  * the flat-vs-radix per-PTE-line cost gap (the serving cost model's
    ``pte_line``) grows,

with an explicit VERDICT string, merged into ``BENCH_sim.json`` under a
``"memory_model"`` section (merge-not-clobber, like every other
section).

Dispatch shape: each grid is ONE bucketed sweep — ``memory_model`` is a
SHAPE axis (bank geometry is compiled in) and everything else rides the
batch lanes, so the whole 2-model x 2-mechs x W-workload grid costs one
``simulate_batch_varied`` dispatch per (machine-shape, walk-fn) bucket.
The driver runs at a chunk size no other stage uses, so the runner
cache is cold and ``compile count == new bucket count`` is ASSERTED,
not just reported.

Usage:  python benchmarks/sim_memory.py [--fast]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import OrderedDict
from typing import Dict, List, Tuple

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

Row = Tuple[str, float, str]

#: the two memory models every grid crosses
MODELS = ("bounded_linear", "banked")


def _grid(mech_pairs) -> "OrderedDict[str, Tuple]":
    from repro.configs.ndp_sim import SWEEP_WORKLOADS
    return OrderedDict([("memory_model", MODELS),
                        ("mechs", mech_pairs),
                        ("workload", SWEEP_WORKLOADS)])


def _mean_speedup(r, model: str, mechs: Tuple[str, ...], mech: str):
    """(workload,) speedup-over-radix array for one (model, mechs) row."""
    return r.select(memory_model=model, mechs=mechs).map(
        lambda x: x.speedup_vs()[mech])


class _CompileLedger:
    """Tracks (shape, walk-fn) bucket keys across the driver's sweep
    calls and asserts each call compiled EXACTLY its unseen buckets —
    the one-dispatch-per-bucket property as a hard gate, robust to the
    second grid legitimately reusing the first grid's shapes."""

    def __init__(self):
        self.seen: set = set()
        self.ok = True
        self.detail: List[str] = []

    def check(self, name: str, stats: Dict) -> None:
        new = 0
        for b in stats["per_bucket"]:
            key = (b["shape"], tuple(b["walk_fns"]))
            if key not in self.seen:
                self.seen.add(key)
                new += 1
        got = stats["runner_compiles"]
        self.ok = self.ok and (got == new)
        self.detail.append(f"{name}: {got} compiles for {new} new of "
                           f"{stats['buckets']} buckets")
        assert got == new, (
            f"{name}: expected one compile per new (shape, walk-fn) "
            f"bucket ({new}), runner cache reports {got}")


def _line_cost_gap() -> Dict:
    """The serving cost model's flat-vs-radix ``pte_line`` gap under
    each memory model: derive :class:`TranslationCostModel` on the SAME
    ndp serving machine with bounded vs banked DRAM and compare what a
    radix node line costs vs a flat-row line (positive gap = the flat
    organization's extra lines are cheaper)."""
    from repro.configs.ndp_sim import ndp_machine
    from repro.sim import apply_param
    from repro.sim.cost_model import TranslationCostModel
    out: Dict = {}
    for model in MODELS:
        mach = apply_param(ndp_machine(4), "memory_model", model)
        cm = TranslationCostModel.from_sim(mach)
        radix, flat = cm.cost("radix"), cm.cost("ndpage")
        out[model] = {
            "pte_line_radix": radix.pte_line,
            "pte_line_flat": flat.pte_line,
            "gap": round(radix.pte_line - flat.pte_line, 3),
            "dram_line_contiguous": mach.memory.line_cycles(True),
            "dram_line_scattered": mach.memory.line_cycles(False),
        }
    out["gap_grows"] = bool(out["banked"]["gap"]
                            > out["bounded_linear"]["gap"])
    return out


def run_memory_model(fast: bool) -> Tuple[List[Row], Dict]:
    from repro.configs.ndp_sim import PRESETS
    from repro.sim import sweep

    sim_preset = PRESETS["smoke" if fast else "full"]
    # a chunk no other benchmark stage uses: every bucket's runner is a
    # cold cache entry, so the compile==bucket assertion is meaningful
    chunk = sim_preset.chunk + 64
    ledger = _CompileLedger()
    rows: List[Row] = []
    section: Dict = {"preset": sim_preset.name, "chunk": chunk}

    t0 = time.perf_counter()
    bypass = sweep(_grid((("radix", "ndpage", "ideal"),
                          ("radix", "ndpage_nobyp", "ideal"))),
                   base="ndp", cores=4, preset=sim_preset.name,
                   chunk=chunk)
    ledger.check("bypass", bypass.stats)
    m_on, m_off = bypass.axes["mechs"]
    margins: Dict[str, Dict] = {}
    for model in MODELS:
        on = _mean_speedup(bypass, model, m_on, "ndpage")
        off = _mean_speedup(bypass, model, m_off, "ndpage_nobyp")
        margins[model] = {
            "mean_on": round(float(on.mean()), 4),
            "mean_off": round(float(off.mean()), 4),
            "margin": round(float(on.mean() - off.mean()), 4),
        }
        rows.append((f"memmodel_bypass_{model}", 0.0,
                     f"bypass_on={on.mean():.3f} "
                     f"bypass_off={off.mean():.3f} "
                     f"margin={on.mean() - off.mean():+.4f}"))
    margin_widens = bool(margins["banked"]["margin"]
                         > margins["bounded_linear"]["margin"])
    section["bypass"] = dict(margins, margin_widens=margin_widens)

    flatten = sweep(_grid((("radix", "ndpage", "ideal"),
                           ("radix", "ndpage_pl3", "ideal"))),
                    base="ndp", cores=4, preset=sim_preset.name,
                    chunk=chunk)
    ledger.check("flatten", flatten.stats)
    m_pl2, m_pl3 = flatten.axes["mechs"]
    flat_sec: Dict[str, Dict] = {}
    for model in MODELS:
        pl2 = _mean_speedup(flatten, model, m_pl2, "ndpage")
        pl3 = _mean_speedup(flatten, model, m_pl3, "ndpage_pl3")
        flat_sec[model] = {"mean_pl2": round(float(pl2.mean()), 4),
                           "mean_pl3": round(float(pl3.mean()), 4)}
        rows.append((f"memmodel_flatten_{model}", 0.0,
                     f"pl2={pl2.mean():.3f} pl3={pl3.mean():.3f}"))
    section["flatten"] = flat_sec

    gap = _line_cost_gap()
    section["line_cost"] = gap
    rows.append(("memmodel_line_cost", 0.0,
                 f"flat-vs-radix pte_line gap "
                 f"bounded={gap['bounded_linear']['gap']:+.1f} "
                 f"banked={gap['banked']['gap']:+.1f}"))

    wall = time.perf_counter() - t0
    verdict = (
        f"banked DRAM {'WIDENS' if margin_widens else 'does NOT widen'} "
        f"the L1-bypass margin "
        f"({margins['bounded_linear']['margin']:+.4f} -> "
        f"{margins['banked']['margin']:+.4f}) and the flat-vs-radix "
        f"line-cost gap {'GROWS' if gap['gap_grows'] else 'SHRINKS'} "
        f"({gap['bounded_linear']['gap']:+.1f} -> "
        f"{gap['banked']['gap']:+.1f} cycles/line): row-buffer locality "
        f"{'SUPPORTS' if gap['gap_grows'] else 'does not support'} the "
        f"flattened-table organization")
    section.update(
        verdict=verdict,
        checks={"compiles_match_new_buckets": ledger.ok,
                "line_cost_gap_grows": gap["gap_grows"]},
        compile_accounting=ledger.detail,
        wall_s=round(wall, 2))
    rows.append(("memmodel_verdict", 0.0, verdict))
    rows.append(("memmodel_engine",
                 wall * 1e6 / (bypass.stats["points"]
                               + flatten.stats["points"]),
                 f"{bypass.stats['points'] + flatten.stats['points']}pts "
                 f"{ledger.detail} {wall:.1f}s"))
    return rows, section


def merge_into_bench_json(section: Dict, path: str) -> None:
    """Attach the ``memory_model`` section without clobbering the
    figures/sweeps/serving/search sections already there."""
    data: Dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"# WARNING: could not read existing {path} ({e}); "
                  "rewriting it with the memory_model section only",
                  file=sys.stderr)
    data["memory_model"] = section
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def failed_checks(section: Dict) -> List[str]:
    return [n for n, v in section.get("checks", {}).items() if not v]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--fast", action="store_true",
                   help="smoke-preset windows (CI wall clock)")
    args = p.parse_args(argv)
    fast = args.fast or bool(int(os.environ.get("SIM_FIGS_FAST", "0")))

    from benchmarks.run import _setup_host_devices
    from repro.util.compile_cache import enable_compile_cache
    _setup_host_devices()
    enable_compile_cache()

    rows, section = run_memory_model(fast)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    path = os.path.join(_ROOT, "BENCH_sim.json")
    merge_into_bench_json(section, path)
    print(f"# merged 'memory_model' section into {path}")

    failed = failed_checks(section)
    if failed:
        print(f"# MEMORY-MODEL CHECK FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Translation-costed serving benchmark: the paper's end-to-end claim.

NDPage's headline numbers are APPLICATION-level (14.3% / 9.8% / 30.5%
throughput at 1/4/8 cores), not just PTW latency.  This driver closes
the same loop at the serving layer: it replays two request mixes
through the paged-KV ``ServeEngine`` with a
:class:`repro.sim.cost_model.TranslationCostModel` attached, so every
scheduler-level translation (TranslationCache hit or table-walk miss,
with the rebuilt row's touched-PTE-line counts) is priced under ALL
mechanisms at once, and reports tokens/sec per mechanism.

Request mixes:

  * ``decode_heavy``  — short prompts, long generations: mappings grow
    page by page, versions churn, the translation cache misses often
    (the walk-dominated regime).
  * ``prefill_heavy`` — long prompts, short generations: mappings are
    built at admission and mostly stable (the TLB-hit regime).

One decode loop serves every mechanism — mechanism identity never
enters the jit, so NOTHING recompiles per mechanism; the only
simulator work is the one-shot cost-table derivation (one compile per
machine shape, memoized to ``.trace_cache/``; ``--pinned`` skips even
that and uses the committed table, which is what the CI fast lane
runs).

The ``"serving"`` section lands in ``BENCH_sim.json`` (merged into the
existing file, never clobbering the figures/sweeps/real_traces
sections).  Structural checks fail the run: under BOTH mixes, ndpage
tokens/sec >= radix and ideal is the upper bound.

Usage:
  python benchmarks/serving_translation.py [--smoke] [--pinned]
      [--seed N]
  python benchmarks/run.py --serving          # same, as a stage
"""
from __future__ import annotations

import argparse
import dataclasses
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

#: request mixes: (requests, prompt-length range, new tokens).  The
#: smoke variant trims counts, not structure — same regimes, CI cost.
MIXES: Dict[str, dict] = {
    "decode_heavy": dict(n_requests=8, prompt=(3, 8), new_tokens=16),
    "prefill_heavy": dict(n_requests=6, prompt=(24, 40), new_tokens=4),
}
SMOKE_MIXES: Dict[str, dict] = {
    "decode_heavy": dict(n_requests=4, prompt=(3, 8), new_tokens=8),
    "prefill_heavy": dict(n_requests=3, prompt=(24, 40), new_tokens=3),
}


def _engine_factory():
    """One tiny model + params shared by every mix (compile once)."""
    import jax

    from repro.config import get_arch, smoke_variant
    from repro.models import init_params
    cfg = dataclasses.replace(smoke_variant(get_arch("internlm2-1.8b")),
                              dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def run_serving(fast: bool = True, pinned: bool = False, seed: int = 0
                ) -> Tuple[List[Row], Dict]:
    """``pinned`` serves the committed cost table; otherwise the cost
    model is derived from the simulator (memo when warm), and a broken
    derivation fails the run."""
    from repro.serving import Request, ServeEngine
    from repro.sim.cost_model import TranslationCostModel
    from repro.sim.simulator import runner_cache_info

    info0 = runner_cache_info()
    t0 = time.perf_counter()
    model = TranslationCostModel.for_machine(
        source="pinned" if pinned else "sweep")
    cost_wall = time.perf_counter() - t0
    cost_compiles = runner_cache_info().misses - info0.misses

    cfg, params = _engine_factory()
    mixes = SMOKE_MIXES if fast else MIXES
    rows: List[Row] = []
    summary: Dict = {
        "seed": seed,
        "cost_model": {
            "source": model.source, "machine": model.machine,
            "mechs": list(model.mechs),
            "model_cycles_per_token": model.model_cycles_per_token,
            "runner_compiles": cost_compiles,
            "wall_s": round(cost_wall, 2),
        },
        "mixes": {},
    }
    import numpy as np
    for mi, (mix_name, mix) in enumerate(mixes.items()):
        rng = np.random.default_rng(seed * 1000 + mi)
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
        checks = {
            "ndpage_ge_radix": tps["ndpage"] >= tps["radix"],
            "ideal_upper_bound": all(tps["ideal"] >= v - 1e-9
                                     for v in tps.values()),
            "all_completed": len(done) == mix["n_requests"],
        }
        for m in model.mechs:
            rows.append((f"serving_{mix_name}_{m}", 0.0,
                         f"{tps[m]:.0f} tok/s "
                         f"trans={rep['translation_cycles'][m]:.0f}cyc"))
        ok = all(checks.values())
        rows.append((f"serving_{mix_name}_check", wall * 1e6,
                     f"{'OK' if ok else 'FAIL'} {checks} "
                     f"hits={rep['tcache_hits']} "
                     f"misses={rep['tcache_misses']}"))
        summary["mixes"][mix_name] = {
            "requests": mix["n_requests"],
            "tokens": rep["tokens"], "steps": rep["steps"],
            "tcache_hits": rep["tcache_hits"],
            "tcache_misses": rep["tcache_misses"],
            "tokens_per_sec": {m: round(v, 1) for m, v in tps.items()},
            "translation_cycles": {
                m: round(v, 1)
                for m, v in rep["translation_cycles"].items()},
            "per_step_cycles": {
                m: {k: round(v, 1) for k, v in d.items()}
                for m, d in rep["per_step_cycles"].items()},
            "ndpage_speedup": round(tps["ndpage"] / tps["radix"], 4),
            "checks": checks,
            "wall_s": round(wall, 2),
        }
    return rows, summary


def merge_into_bench_json(summary: Dict, path: str) -> None:
    """Attach the serving table to BENCH_sim.json without clobbering the
    figure-suite / sweeps / real_traces sections already there."""
    data: Dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"# WARNING: could not read existing {path} ({e}); "
                  "rewriting it with the serving section only",
                  file=sys.stderr)
    data["serving"] = summary
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def failed_checks(summary: Dict) -> List[str]:
    """Mix names whose structural checks failed — shared by this CLI
    and run.py --serving so both exit nonzero."""
    return [n for n, s in summary["mixes"].items()
            if not all(s["checks"].values())]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="tiny request mixes (PR fast-lane cost)")
    p.add_argument("--pinned", action="store_true",
                   help="use the committed cost table — no simulator "
                        "run at all (hermetic)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    from benchmarks.run import _setup_host_devices
    from repro.util.compile_cache import enable_compile_cache
    _setup_host_devices()
    enable_compile_cache()

    rows, summary = run_serving(fast=args.smoke, pinned=args.pinned,
                                seed=args.seed)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    path = os.path.join(_ROOT, "BENCH_sim.json")
    merge_into_bench_json(summary, path)
    print(f"# wrote serving section into {path}")

    failed = failed_checks(summary)
    if failed:
        print(f"# SERVING CHECK FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

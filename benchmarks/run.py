"""Benchmark driver: one section per paper figure/table.

Prints ``name,us_per_call,derived`` CSV and writes bench_results.json plus
BENCH_sim.json (per-mechanism cycles + engine wall-clock — the perf
trajectory future PRs compare against).

Sections (stages):
  * Figs 4-8:   address-translation characterization (NDP vs CPU)
  * Figs 12-14: end-to-end speedups of ECH / HugePage / NDPage / Ideal
  * --sweeps:   sensitivity sweeps (benchmarks/sim_sweep.py);
                ``--sweep-presets a,b`` selects a subset
  * --trace-validate: real-vs-synthetic trace comparison
                (benchmarks/trace_validate.py)
  * --serving:  translation-costed serving throughput per mechanism
                (benchmarks/serving_translation.py)
  * --serving-fleet: fleet-scale costed serving — continuous batching
                with prefix sharing and translation-aware admission,
                plus the model-cycles-per-token repricing sweep
                (benchmarks/serving_fleet.py)
  * --search:   seeded design-space search + frontier-regression gate
                (benchmarks/sim_search.py); ``--search-space`` selects
                the space (default: the nightly ``default`` space)
  * --zoo:      related-work mechanism zoo — sim + costed serving +
                zoo-space search + collision analysis, with an
                explicit verdict vs ndpage_search
                (benchmarks/sim_zoo.py)
  * --memory-model: bounded_linear vs banked DRAM comparison — bypass
                margin + flat-vs-radix line-cost gap, with verdict
                (benchmarks/sim_memory.py)

``--fast`` (or SIM_FIGS_FAST=1) runs the simulator figures on the smoke
preset — same engine and orderings, CI wall-clock.

Every requested stage runs even if an earlier one fails, but ANY stage
failure (an exception, or a failed ordering/validation check) makes the
driver exit non-zero.  The end-of-run summary lists EVERY stage —
passed or failed — with its wall time and exit detail (the exception
message for failures), so a broken stage can never hide in the middle
of a green nightly log and slow stages are visible at a glance.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _setup_host_devices() -> None:
    """Honour SIM_DEVICES=N: expose N XLA host devices so simulate_batch
    can shard its B axis.  Must run before any jax backend initialization
    — that is why it lives here and not inside the library."""
    n = os.environ.get("SIM_DEVICES")
    if not n or int(n) <= 1:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={int(n)}"
        ).strip()


def _print_rows(rows) -> None:
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    sys.stdout.flush()


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--fast", action="store_true",
                   help="smoke-preset simulator figures (sub-minute)")
    p.add_argument("--sweeps", action="store_true",
                   help="also run the sensitivity sweeps "
                        "(benchmarks/sim_sweep.py)")
    p.add_argument("--sweep-presets", default=None,
                   help="comma-separated sweep preset subset (default: "
                        "all) — nightly CI runs a reduced grid")
    p.add_argument("--trace-validate", action="store_true",
                   help="also run the real-vs-synthetic trace "
                        "validation (benchmarks/trace_validate.py)")
    p.add_argument("--serving", action="store_true",
                   help="also run the translation-costed serving "
                        "benchmark (benchmarks/serving_translation.py)")
    p.add_argument("--serving-fleet", action="store_true",
                   help="also run the fleet-scale costed serving "
                        "benchmark — continuous batching, prefix "
                        "sharing, translation-aware admission "
                        "(benchmarks/serving_fleet.py)")
    p.add_argument("--search", action="store_true",
                   help="also run the seeded design-space search and "
                        "frontier-regression gate "
                        "(benchmarks/sim_search.py)")
    p.add_argument("--search-space", default="default",
                   help="SEARCH_SPACES name for --search")
    p.add_argument("--zoo", action="store_true",
                   help="also run the related-work mechanism zoo "
                        "comparison (benchmarks/sim_zoo.py)")
    p.add_argument("--memory-model", action="store_true",
                   help="also run the bounded-vs-banked DRAM memory "
                        "model comparison (benchmarks/sim_memory.py)")
    p.add_argument("--stage-timeout", type=float,
                   default=float(os.environ.get("BENCH_STAGE_TIMEOUT",
                                                "0") or 0),
                   help="wall-clock seconds per stage; a stage still "
                        "running at the deadline is reported TIMEOUT "
                        "(distinct from FAIL), later stages still run, "
                        "and the driver exits non-zero (0 = no limit; "
                        "env BENCH_STAGE_TIMEOUT)")
    args = p.parse_args(argv)
    if args.fast:
        os.environ["SIM_FIGS_FAST"] = "1"
    fast = args.fast or bool(int(os.environ.get("SIM_FIGS_FAST", "0")))

    _setup_host_devices()
    from repro.util.compile_cache import enable_compile_cache
    enable_compile_cache()
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    bench_sim_path = os.path.join(root, "BENCH_sim.json")

    # each stage runs isolated: a raising stage is RECORDED (and the
    # driver exits non-zero at the end) but never silently aborts the
    # stages after it — nightly logs show every failure, masked by none.
    # Every stage's outcome, wall time and exit detail land in the
    # end-of-run summary: PASS, FAIL, or TIMEOUT (a stage that was
    # still running at --stage-timeout; the hung thread is abandoned
    # and the remaining stages run on the main thread as usual).
    from repro.util import resilience
    stage_reports: list = []    # (name, status, wall_s, detail)

    def stage(name, fn):
        t0 = time.time()
        try:
            resilience.watchdog_call(fn, args.stage_timeout,
                                     tag=f"stage:{name}", retries=0)
        except resilience.DispatchTimeout as e:
            detail = str(e)
            stage_reports.append((name, "TIMEOUT", time.time() - t0,
                                  detail))
            print(f"# STAGE TIMEOUT: {name} ({detail})", file=sys.stderr)
        except Exception as e:
            traceback.print_exc()
            detail = f"{type(e).__name__}: {e}"
            stage_reports.append((name, "FAIL", time.time() - t0, detail))
            print(f"# STAGE FAILED: {name} ({detail})", file=sys.stderr)
        else:
            stage_reports.append((name, "PASS", time.time() - t0, "ok"))

    rows: list = []
    summary: dict = {}
    print("name,us_per_call,derived")
    sys.stdout.flush()

    def write_bench_results():
        # rewritten after every row-producing stage so a later stage
        # failing never costs the rows already measured
        out = {
            "rows": [{"name": n, "us_per_call": u, "derived": d}
                     for n, u, d in rows],
            "speedup_summary": summary,
        }
        with open(os.path.join(root, "bench_results.json"), "w") as f:
            json.dump(out, f, indent=1)
        print(f"# wrote {os.path.join(root, 'bench_results.json')}")

    def st_figures():
        t0 = time.time()
        from benchmarks import sim_figures
        fig_rows, fig_summary = sim_figures.run_all()
        sim_wall = time.time() - t0
        _print_rows(fig_rows)
        rows.extend(fig_rows)
        summary.update(fig_summary)
        write_bench_results()

        bench_sim = dict(fig_summary.get("perf", {}))
        bench_sim["figures_wall_s"] = round(sim_wall, 2)
        bench_sim["speedups"] = {k: v for k, v in fig_summary.items()
                                 if k != "perf"}
        with open(bench_sim_path, "w") as f:
            json.dump(bench_sim, f, indent=1)
        print(f"# wrote {bench_sim_path} "
              f"(figures wall {sim_wall:.1f}s)")

    def st_sweeps():
        # sensitivity sweeps merge their section into BENCH_sim.json
        from benchmarks import sim_sweep
        presets = (args.sweep_presets.split(",") if args.sweep_presets
                   else list(sim_sweep._HANDLERS))
        srows, ssummary = sim_sweep.run_sweeps(presets, fast=fast)
        _print_rows(srows)
        sim_sweep.merge_into_bench_json(ssummary, bench_sim_path)
        failed = sim_sweep.failed_checks(ssummary)
        if failed:
            raise RuntimeError(f"sweep ordering checks FAILED: {failed}")

    def st_trace_validate():
        from benchmarks import trace_validate
        vrows, vsummary = trace_validate.run_validation(fast=fast)
        _print_rows(vrows)
        trace_validate.merge_into_bench_json(vsummary, bench_sim_path)
        failed = trace_validate.failed_checks(vsummary)
        if failed:
            raise RuntimeError(f"real-trace checks FAILED: {failed}")

    def st_serving():
        from benchmarks import serving_translation
        # always the FULL request mixes: --fast trims the simulator
        # figure preset, but the serving mixes are cheap even at full
        # size and the PR lane already covers the smoke variant
        # (serving_translation.py --smoke --pinned).  The cost model is
        # derived from the simulator, so a broken derivation FAILS the
        # stage.
        srows, ssummary = serving_translation.run_serving(fast=False)
        _print_rows(srows)
        serving_translation.merge_into_bench_json(ssummary,
                                                  bench_sim_path)
        failed = serving_translation.failed_checks(ssummary)
        if failed:
            raise RuntimeError(f"serving ordering checks FAILED: {failed}")

    def st_serving_fleet():
        from benchmarks import serving_fleet
        # full fleet mix + the mcpt sweep; a broken cost-model
        # derivation fails the stage (the PR lane covers the hermetic
        # smoke variant: serving_fleet.py --smoke --pinned)
        frows, fsummary = serving_fleet.run_fleet(fast=False)
        _print_rows(frows)
        serving_fleet.merge_into_bench_json(fsummary, bench_sim_path)
        failed = serving_fleet.failed_checks(fsummary)
        if failed:
            raise RuntimeError(f"fleet serving gates FAILED: {failed}")

    def st_search():
        from benchmarks import sim_search
        srows, ssummary = sim_search.run_search(args.search_space)
        _print_rows(srows)
        sim_search.merge_into_bench_json(ssummary, bench_sim_path)
        failed = sim_search.failed_checks(ssummary)
        if failed:
            raise RuntimeError(f"search gates FAILED: {failed}")

    def st_zoo():
        from benchmarks import sim_zoo
        srows, ssummary = sim_zoo.run_all(fast=fast)
        _print_rows(srows)
        sim_zoo.merge_into_bench_json(ssummary, bench_sim_path)
        failed = sim_zoo.failed_checks(ssummary)
        if failed:
            raise RuntimeError(f"zoo checks FAILED: {failed}")

    def st_memory_model():
        from benchmarks import sim_memory
        mrows, msection = sim_memory.run_memory_model(fast=fast)
        _print_rows(mrows)
        rows.extend(mrows)
        write_bench_results()
        sim_memory.merge_into_bench_json(msection, bench_sim_path)
        failed = sim_memory.failed_checks(msection)
        if failed:
            raise RuntimeError(f"memory-model checks FAILED: {failed}")

    stage("figures", st_figures)
    if args.sweeps:
        stage("sweeps", st_sweeps)
    if args.trace_validate:
        stage("trace_validate", st_trace_validate)
    if args.serving:
        stage("serving", st_serving)
    if args.serving_fleet:
        stage("serving_fleet", st_serving_fleet)
    if args.search:
        stage("search", st_search)
    if args.zoo:
        stage("zoo", st_zoo)
    if args.memory_model:
        stage("memory_model", st_memory_model)

    # the per-stage summary: every stage with wall time and exit detail
    # — failures quote the exception, timeouts the abandoned deadline,
    # successes say ok.  Recovery events (quarantines, watchdog
    # retries, preemptions) taken along the way are listed so a PASS
    # that leaned on the resilience layer is visible as such.
    print("# stage summary:")
    for name, status, wall, detail in stage_reports:
        print(f"#   {status:<7} {name:<16} {wall:7.1f}s  {detail}")
    events = resilience.recovery_events()
    if events:
        print("# recovery events:")
        for kind, detail in events:
            print(f"#   {kind}: {detail}")
    failures = [(n, s, d) for n, s, _, d in stage_reports if s != "PASS"]
    if failures:
        sys.exit("benchmark stages FAILED: "
                 + "; ".join(f"{n} ({s}: {d})" for n, s, d in failures))


if __name__ == "__main__":
    main()

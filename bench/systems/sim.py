"""The translation simulator (``repro.sim``) under a bucket-job mix.

Set-up generates the mix's traces from the seed, compiles the chunk
runner on one chunk of them, and warms the per-chunk slices of a whole
job.  The window then runs the whole bucket job through
``simulate_batch`` back to back, the timings of each job recorded, until
``--seconds`` have passed; a job that has started runs to its end.  The
traced run traces the second job whole (the first where only one ran).

``correct``: every lane of every job in the window is compared with the
plain reference (``bench/reference/sim_ref.py``) run on the same traces:
the integer counters exactly, and the cycle results by their largest
relative difference.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from harness import table2
from reference import sim_ref

#: largest relative cycle difference from the reference; set from the
#: program's readings and the bfloat16 control's (PERF.md section 2)
CYCLES_LIMIT = 2e-4


def machine_config(m):
    """The program's MachineConfig with the configuration's numbers."""
    from repro.configs.ndp_sim import CacheParams, MachineConfig, TLBParams
    cache = lambda p: CacheParams(p["size_bytes"], p["ways"],   # noqa: E731
                                  p["latency"]) if p else None
    mem = m["memory"]
    return MachineConfig(
        name=f"{m['kind']}-{m['num_cores']}c", is_ndp=m["kind"] == "ndp",
        num_cores=m["num_cores"], l1d=cache(m["l1d"]), l2=cache(m["l2"]),
        l3=cache(m["l3"]),
        l1_dtlb=TLBParams(m["l1_dtlb"]["entries"], m["l1_dtlb"]["ways"],
                          m["l1_dtlb"]["latency"]),
        l2_tlb=TLBParams(m["l2_tlb"]["entries"], m["l2_tlb"]["ways"],
                         m["l2_tlb"]["latency"]),
        pwc_entries=m["pwc_entries"], pwc_latency=m["pwc_latency"],
        memory=dict(latency=mem["latency"], service=mem["service"],
                    bandwidth_gbs=mem["bandwidth_gbs"]))


def warm_slices(n_lanes: int, cores: int, length: int, chunk: int,
                chips: int) -> None:
    """Compile the per-chunk slices a whole job takes of its packed
    inputs ((T, lanes, cores) and (T, lanes)), which a one-chunk warm-up
    does not reach."""
    import jax
    import jax.numpy as jnp
    t_pad = length + (-length) % chunk
    lanes = n_lanes + (-n_lanes) % chips
    for dt in (np.int32, np.float32, np.bool_):
        for shape in ((t_pad, lanes, cores), (t_pad, lanes)):
            a = jnp.asarray(np.zeros(shape, dt))
            if chips > 1:
                from jax.sharding import Mesh, NamedSharding, PartitionSpec
                mesh = Mesh(np.asarray(jax.devices()[:chips]), ("b",))
                a = jax.device_put(a, NamedSharding(
                    mesh, PartitionSpec(None, "b")))
            for i in range(0, t_pad, chunk):
                a[i:i + chunk].block_until_ready()


def run(r) -> None:
    from repro.sim import simulate_batch
    cfg, mix = r.cell.config, r.cell.traffic
    m = cfg["machine"]
    cores, chunk = m["num_cores"], mix["chunk"]
    length = cfg["accesses_per_core"]
    mach = machine_config(m)
    mechs = tuple(x["name"] for x in cfg["mechanisms"])
    chips = r.cell.chips
    traces = table2.generate_suite(mix["workloads"], cores, length, r.seed)

    def job(n=None, timings=None):
        return simulate_batch(mach, traces, n, mechs=mechs, chunk=chunk,
                              devices=chips, timings=timings)

    warm = {}
    job(chunk, warm)
    warm_slices(len(traces), cores, length, chunk, chips)
    compiles = r.compile_counter
    c0 = compiles.count
    t0 = r.setup_done()
    jobs = []
    while time.perf_counter() - t0 < r.seconds or not jobs:
        with r.traced() if len(jobs) == 1 else contextlib.nullcontext():
            tm = {}
            j0 = time.perf_counter()
            with r.span("bench.job"):
                res = job(timings=tm)
            j1 = time.perf_counter()
        jobs.append({"start": j0 - t0, "end": j1 - t0, "timings": tm,
                     "results": res})
    if r.trace and not r._traced:
        with r.traced():
            tm = {}
            res = job(timings=tm)
    r.host.update(
        window_s=jobs[-1]["end"], jobs=jobs, compile_s=warm["compile_s"],
        window_compiles=compiles.count - c0,
        work_per_job=len(traces) * cores * length * len(mechs),
        chunk=chunk)
    r.read_memory_peak()
    for j in jobs:
        j["results"] = [_numbers(x) for x in j["results"]]
    check(r, cfg, mix, traces, [j["results"] for j in jobs])


def _numbers(res):
    return {k: np.asarray(getattr(res, k), np.float64)
            for k in sim_ref.COUNTERS + sim_ref.CYCLES}


def check(r, cfg, mix, traces, jobs) -> None:
    """Every lane of every job against the plain reference."""
    ref = sim_ref.simulate(cfg["machine"], cfg["mechanisms"], traces,
                           mix["chunk"])
    r.host["reference"] = (traces, ref)
    mismatched, worst, failed_lanes = compare(jobs, ref)
    ok = r.compare("counter_mismatches", mismatched, 0)
    ok &= r.compare("cycles_rel_max", worst, CYCLES_LIMIT)
    r.correct = bool(ok)
    r.attempted = len(jobs) * len(ref)
    r.failed = failed_lanes


def compare(jobs, ref):
    """(counter arrays that differ, largest relative cycle difference,
    lanes that fail) of every job's lanes against the reference's."""
    mismatched = 0
    worst = 0.0
    failed_lanes = 0
    for results in jobs:
        if len(results) != len(ref):
            mismatched += abs(len(ref) - len(results)) * len(sim_ref.COUNTERS)
            failed_lanes += abs(len(ref) - len(results))
        for got, want in zip(results, ref):
            bad = sum(int(not np.array_equal(got[k], want[k]))
                      for k in sim_ref.COUNTERS)
            rel = max(float(np.max(np.abs(got[k] - want[k])
                                   / np.maximum(np.abs(want[k]), 1.0)))
                      for k in sim_ref.CYCLES)
            if not np.isfinite(rel):
                rel = float("inf")
            mismatched += bad
            worst = max(worst, rel)
            failed_lanes += int(bad > 0 or rel > CYCLES_LIMIT)
    return mismatched, worst, failed_lanes


def control(r):
    """The bfloat16 control's readings: the reference computed with every
    cycle term in bfloat16, in the program's place."""
    traces, ref = r.host["reference"]
    cfg, mix = r.cell.config, r.cell.traffic
    import ml_dtypes
    low = sim_ref.simulate(cfg["machine"], cfg["mechanisms"], traces,
                           mix["chunk"], cycle_dtype=ml_dtypes.bfloat16)
    low = [{k: np.asarray(v, np.float64) for k, v in lane.items()}
           for lane in low]
    mismatched, worst, _ = compare([low], ref)
    return {"counter_mismatches": mismatched, "cycles_rel_max": worst}

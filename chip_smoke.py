"""Bring-up check: drive both halves of the system once on one TPU chip.

  python chip_smoke.py              # one chip: the three phases below
  python chip_smoke.py --chips 4    # four chips: the sharded simulation
                                    # fleet against one device, only

Phases (one chip):

1. device     -- JAX's first device must be a TPU; there is no CPU fallback.
2. simulator  -- the figure suite's six buckets ({ndp, cpu} x {1, 4, 8}
   cores, the 11 Table-II workloads on the batch axis, ``full`` preset)
   through ``simulate_batch``; then the ndp 8-core bucket again on the
   host CPU.  Integer counters must match exactly and cycles within
   ``CYCLES_RTOL``; the NDP buckets must keep ideal > ndpage > 1.0 (the
   suite-mean speedups over radix of Figs. 12-14).
3. serving    -- internlm2-1.8b at its published widths in bf16 (random
   weights from a fixed seed) through ``repro.launch.serve``: 8 requests,
   once with the flat and once with the radix block table.  Every request
   must complete with the same tokens in both modes, the compiled decode
   step must hold the Pallas kernel (``tpu_custom_call``), and the kernel
   must match ``paged_attention_ref`` on one decode step's real inputs.

Progress goes to stdout; any failed check raises.  The last line of a
passing run is one JSON object naming the device.  Traces are generated
from seeds in-process; the trace cache stays off.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the Pallas kernel's mark in a compiled TPU program
KERNEL_MARKER = "tpu_custom_call"
#: kernel-vs-reference bound on |kernel - ref|, as a multiple of the
#: largest |v| attended.  Both sides take the softmax in f32 and round
#: the attention weights to bf16 (8 significant bits: relative error
#: <= 2^-9) before the PV product, and round the output to bf16; the
#: output is a convex combination of v rows, so the two differ by at
#: most 4 * 2^-9 * max|v|.
KERNEL_TOL = 4 * 2.0 ** -9
#: decode steps served before the kernel is checked on the live state
KERNEL_CHECK_STEP = 40


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def require_tpu(chips: int):
    """The devices to run on; exits when JAX finds no TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (first device: "
                 f"{devs[0].platform}); this check runs on a chip only")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"JAX found {len(devs)}")
    return devs


def sim_setup():
    """(preset, buckets): the paper-figure preset and the figure suite's
    (machine, cores) buckets."""
    from repro.configs.ndp_sim import CORE_COUNTS, PRESETS
    return PRESETS["full"], [(m, c) for c in CORE_COUNTS
                             for m in ("ndp", "cpu")]


def serve_setup():
    """(architecture, deployment) served by phase 3."""
    from repro import config as C
    from repro.launch import serve
    return C.get_arch("internlm2-1.8b"), serve.FULL


# ---------------------------------------------------------------------------
# simulator
# ---------------------------------------------------------------------------
INT_COUNTERS = ("walks", "l1tlb_misses", "pte_accesses", "pte_l1_hits",
                "pte_mem", "data_l1_misses", "data_mem")
FLOAT_RESULTS = ("cycles", "trans_cycles", "walk_cycles")


def _bucket_traces(cores: int, preset):
    from repro.configs.ndp_sim import WORKLOADS
    from repro.workloads import generate_traces
    t0 = time.perf_counter()
    traces = generate_traces(list(WORKLOADS), cores, preset=preset)
    return traces, time.perf_counter() - t0


def _simulate(machine: str, cores: int, traces, preset, devices: int):
    from repro.configs.ndp_sim import cpu_machine, ndp_machine
    from repro.sim import simulate_batch
    mach = ndp_machine(cores) if machine == "ndp" else cpu_machine(cores)
    tm: dict = {}
    res = simulate_batch(mach, traces, chunk=preset.chunk, devices=devices,
                         timings=tm)
    return res, tm


def compare_results(a, b, what: str) -> float:
    """Integer counters equal, float results within CYCLES_RTOL; returns
    the largest relative float difference."""
    import numpy as np
    from repro.sim.simulator import CYCLES_RTOL
    worst = 0.0
    for i, (ra, rb) in enumerate(zip(a, b)):
        for name in INT_COUNTERS:
            check(np.array_equal(getattr(ra, name), getattr(rb, name)),
                  f"{what}: lane {i} {name} differs")
        for name in FLOAT_RESULTS:
            x, y = getattr(ra, name), getattr(rb, name)
            rel = float(np.max(np.abs(x - y) / np.maximum(np.abs(y), 1.0)))
            worst = max(worst, rel)
            check(rel <= CYCLES_RTOL,
                  f"{what}: lane {i} {name} rel diff {rel:.3g} > "
                  f"{CYCLES_RTOL:g}")
    return worst


def _suite_speedups(results):
    import numpy as np
    sp = [r.speedup_vs() for r in results]
    return {m: float(np.mean([s[m] for s in sp])) for m in sp[0]}


def sim_phase() -> None:
    import jax
    from repro.sim.simulator import CYCLES_RTOL
    preset, buckets = sim_setup()
    log("sim", f"preset={preset.name} trace_len={preset.trace_len} "
               f"chunk={preset.chunk} buckets={buckets}")
    kept = {}
    for machine, cores in buckets:
        traces, gen_s = _bucket_traces(cores, preset)
        res, tm = _simulate(machine, cores, traces, preset, devices=1)
        sp = _suite_speedups(res)
        log("sim", f"{machine}-{cores}c B={len(res)} trace_gen_s={gen_s:.3f} "
                   f"compile_s={tm['compile_s']:.3f} run_s={tm['run_s']:.3f} "
                   f"chunks={tm['chunks']} ndpage={sp['ndpage']:.4f} "
                   f"ideal={sp['ideal']:.4f}")
        if machine == "ndp":
            check(sp["ideal"] > sp["ndpage"] > 1.0,
                  f"{machine}-{cores}c ordering ideal > ndpage > 1.0: {sp}")
        if (machine, cores) == ("ndp", 8):
            kept = dict(traces=traces, res=res)
    check(bool(kept), "the ndp-8c bucket ran")
    with jax.default_device(jax.devices("cpu")[0]):
        ref, tm = _simulate("ndp", 8, kept["traces"], preset, devices=1)
    worst = compare_results(kept["res"], ref, "ndp-8c chip vs cpu")
    log("sim", f"ndp-8c on cpu compile_s={tm['compile_s']:.3f} "
               f"run_s={tm['run_s']:.3f}; counters exact, cycles max rel "
               f"diff {worst:.3g} (rtol {CYCLES_RTOL:g})")


def sharded_sim_phase(chips: int) -> None:
    """--chips N: the ndp-8c bucket sharded over N devices vs one."""
    preset, _ = sim_setup()
    traces, _ = _bucket_traces(8, preset)
    runs = {}
    for n in (chips, 1):
        runs[n], tm = _simulate("ndp", 8, traces, preset, devices=n)
        log("sim", f"ndp-8c devices={n} compile_s={tm['compile_s']:.3f} "
                   f"run_s={tm['run_s']:.3f}")
    worst = compare_results(runs[chips], runs[1],
                            f"ndp-8c devices={chips} vs 1")
    log("sim", f"devices={chips} vs 1: counters exact, cycles max rel diff "
               f"{worst:.3g}")


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def _decode_args(eng, mode: str):
    """Arguments of the engine's decode step, shaped as it passes them."""
    import jax.numpy as jnp
    from repro.core import block_table as BT
    b, maxp = eng.max_batch, eng.kvm.max_pages
    table = jnp.zeros((b, maxp), jnp.int32)
    if mode == BT.RADIX:
        table = BT.radix_from_flat(table, leaf_size=min(16, maxp))
    state = dict(eng.state, table=table, lengths=jnp.zeros((b,), jnp.int32))
    return eng.params, eng.cfg, state, jnp.zeros((b,), jnp.int32)


def kernel_vs_reference(eng) -> float:
    """The paged kernel against ``paged_attention_ref`` on the live
    engine's layer-0 inputs for its next decode step: real K/V pools,
    block table, lengths and query.  Returns |kernel - ref| / max|v|."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    from repro.models.layers import apply_rope, rmsnorm

    cfg, p = eng.cfg, eng.params
    slots, rows, lens = [], [], []
    for sid in eng.sched.active_seqs():
        slot = eng.sched.slot_of[sid]
        row = np.full(eng.kvm.max_pages, -1, np.int32)
        pages = eng.kvm.pages[sid]
        row[:len(pages)] = pages
        slots.append(slot)
        rows.append(row)
        lens.append(int(eng._prompt_pos[slot]))     # tokens in the cache
    check(len(slots) > 0 and min(lens) > 0, "live sequences to attend")
    table = jnp.asarray(np.stack(rows))
    lens = jnp.asarray(lens, jnp.int32)
    toks = jnp.asarray(eng._next_token[slots])

    lp = jax.tree.map(lambda a: a[0], p["stack"]["scan"]["block_0"])
    pools = jax.tree.map(lambda a: a[0], eng.state["stack"]["scan"]["block_0"])
    h = rmsnorm(lp["norm1"], p["embed"][toks][:, None, :], cfg.rms_norm_eps)
    q = (h @ lp["mixer"]["wq"]).reshape(len(slots), 1, cfg.num_heads,
                                        cfg.head_dim)
    q = apply_rope(q, lens[:, None], cfg.rope_theta)

    got = ops.paged_attention(q, pools["kp"], pools["vp"], table, lens)
    want = jax.jit(ref.paged_attention_ref)(q, pools["kp"], pools["vp"],
                                            table, lens)
    vmax = float(jnp.abs(pools["vp"][jnp.maximum(table, 0)]).max())
    err = float(jnp.abs(got.astype(jnp.float32)
                        - want.astype(jnp.float32)).max())
    return err / max(vmax, 1e-30)


def serve_phase() -> None:
    import jax
    import numpy as np
    from repro.core import block_table as BT
    from repro.launch import serve
    from repro.models import decode_step, init_params

    cfg, run = serve_setup()
    log("serve", f"{cfg.name}: layers={cfg.num_layers} d_model={cfg.d_model} "
                 f"heads={cfg.num_heads}/{cfg.num_kv_heads} "
                 f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} "
                 f"vocab={cfg.vocab_size} dtype={cfg.dtype}; {run}")
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, jax.random.PRNGKey(0)))
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    log("serve", f"random weights: {n_params} params in "
                 f"{time.perf_counter() - t0:.3f}s")
    reqs = serve.make_requests(run["requests"], cfg.vocab_size,
                               run["prompt_len"], run["new_tokens"])
    log("serve", f"prompt lengths {[len(r.prompt) for r in reqs]}, "
                 f"{run['new_tokens']} new tokens each")

    generated = {}
    for mode in (BT.FLAT, BT.RADIX):
        eng = serve.start(cfg, params, mode, reqs,
                          max_batch=run["max_batch"], max_len=run["max_len"],
                          page_size=run["page_size"])
        t0 = time.perf_counter()
        compiled = decode_step.lower(*_decode_args(eng, mode),
                                     kv_mode=mode).compile()
        compile_s = time.perf_counter() - t0
        has_kernel = KERNEL_MARKER in compiled.as_text()
        log("serve", f"{mode}: decode step compile_s={compile_s:.3f} "
                     f"{KERNEL_MARKER}={has_kernel}")
        check(has_kernel, f"{mode} decode step runs the Pallas kernel")

        t0 = time.perf_counter()
        done = eng.run(max_steps=KERNEL_CHECK_STEP)
        if mode == BT.FLAT:
            t1 = time.perf_counter()
            err = kernel_vs_reference(eng)
            log("serve", f"kernel vs paged_attention_ref after "
                         f"{KERNEL_CHECK_STEP} steps: max|diff|/max|v| = "
                         f"{err:.3g} (tol {KERNEL_TOL:.3g})")
            check(err <= KERNEL_TOL, "paged kernel matches the reference")
            t0 += time.perf_counter() - t1
        done += eng.run()
        run_s = time.perf_counter() - t0
        stats = eng.sched.stats
        log("serve", f"{mode}: served {len(done)} requests in "
                     f"{stats['steps']} decode steps, run_s={run_s:.3f}")
        check(len(done) == len(reqs)
              and all(r.failed is None
                      and len(r.generated) == run["new_tokens"]
                      for r in done), f"{mode}: every request completes")
        generated[mode] = {r.req_id: list(r.generated) for r in done}
    check(generated[BT.FLAT] == generated[BT.RADIX],
          "flat and radix tables generate identical tokens")
    log("serve", "flat and radix generated identical tokens")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log("serve", f"peak device bytes in use: {stats['peak_bytes_in_use']}")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded simulation fleet "
                         "against its one-device run")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"chip_smoke: no src/repro beside {__file__}; run it from "
                 "a checkout of the repository")
    sys.path.insert(0, src)
    # traces come from seeds in this process, never from a cache on disk
    os.environ["SIM_TRACE_CACHE"] = "0"
    # the simulator's reference run needs the host CPU as a second backend
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    import jax
    devs = require_tpu(args.chips)
    from repro.util.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    log("device", f"{len(devs)} x {devs[0].device_kind} "
                  f"({devs[0].platform}); jax {jax.__version__}; "
                  f"compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips > 1:
        sharded_sim_phase(args.chips)
    else:
        sim_phase()
        serve_phase()
    log("done", f"wall {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""simulate_batch: lanes independent of their neighbours, agreement
with the plain numpy reference, B-axis sharding, and SimResult slicing
helpers."""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs.ndp_sim import cpu_machine, ndp_machine
from repro.sim import simulate, simulate_batch
from repro.sim import simulator as S
from repro.sim.mechanisms import DEFAULT_MECHS
from repro.workloads import generate_traces

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "bench")

WORKLOADS3 = ("rnd", "bc", "bfs")
LEN = 700          # spans a chunk boundary at chunk=512


def _assert_results_equal(a, b, msg=""):
    """Counter-for-counter equality of two SimResults."""
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if isinstance(va, np.ndarray):
            np.testing.assert_array_equal(va, vb,
                                          err_msg=f"{msg}: {f.name}")
        else:
            assert va == vb, f"{msg}: {f.name}"


def _sim_ref():
    """The benchmark's plain numpy reference of the timing model."""
    spec = importlib.util.spec_from_file_location(
        "sim_ref", os.path.join(BENCH, "reference", "sim_ref.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestBitExact:
    """A lane's result must not depend on its neighbours or on its
    position in the batch: simulate() (a batch of one) and one lane of
    simulate_batch agree exactly — every counter and cycle, not just
    within tolerance."""

    @pytest.mark.parametrize("cores", [1, 2])
    def test_batch_equals_loop_ndp(self, cores):
        mach = ndp_machine(cores)
        traces = generate_traces(WORKLOADS3, cores, length=LEN, seed=7)
        singles = [simulate(mach, tr, chunk=512) for tr in traces]
        batched = simulate_batch(mach, traces, chunk=512)
        assert len(batched) == len(traces)
        for w, s, b in zip(WORKLOADS3, singles, batched):
            _assert_results_equal(s, b, msg=f"ndp{cores} {w}")

    def test_batch_equals_loop_cpu_with_pl3(self):
        # the CPU hierarchy (L2+L3) and a registered extension mechanism
        # keep a lane independent of its neighbours too
        mach = cpu_machine(2)
        names = DEFAULT_MECHS + ("ndpage_pl3",)
        traces = generate_traces(WORKLOADS3[:2], 2, length=LEN, seed=7)
        singles = [simulate(mach, tr, chunk=512, mechs=names)
                   for tr in traces]
        batched = simulate_batch(mach, traces, chunk=512, mechs=names)
        for s, b in zip(singles, batched):
            assert b.mechs == names
            _assert_results_equal(s, b, msg="cpu2+pl3")

    def test_mixed_trace_lengths(self):
        # lanes with different true lengths are masked per-sim: a short
        # lane beside a long one equals its own batch of one
        mach = ndp_machine(1)
        t_long = generate_traces(("rnd",), 1, length=LEN, seed=7)[0]
        t_short = generate_traces(("bc",), 1, length=300, seed=7)[0]
        singles = [simulate(mach, t_long, chunk=512),
                   simulate(mach, t_short, chunk=512)]
        batched = simulate_batch(mach, [t_long, t_short], chunk=512)
        for s, b in zip(singles, batched):
            _assert_results_equal(s, b, msg="mixed-length")
        assert batched[0].accesses == LEN
        assert batched[1].accesses == 300

    def test_empty_batch(self):
        assert simulate_batch(ndp_machine(1), []) == []


@pytest.mark.parametrize("cores", [1, 2])
def test_simulate_matches_numpy_reference(cores):
    """The one engine against the benchmark's plain numpy reference
    (``bench/reference/sim_ref.py``, none of the simulator's code) on
    the paper's NDP machine: integer counters exactly, the cycle results
    to CYCLES_RTOL.  The reference takes the machine and mechanisms from
    the ndp-8c configuration at this core count, with the simulator's
    calibration for it (huge-page fragmentation and stall, ECH rehash)."""
    ref = _sim_ref()
    with open(os.path.join(BENCH, "configs", "ndp-8c.json")) as f:
        cfg = json.load(f)
    machine = dict(
        cfg["machine"], num_cores=cores,
        fragmented_4k_share=S.FRAC_4K[cores],
        hugepage_stall_cycles=S.HP_STALL_BASE
        + S.HP_STALL_PER_CORE * (cores - 1),
        ech_rehash_cycles=S.ECH_REHASH_QUAD * max(cores - 2, 0) ** 2)
    traces = generate_traces(WORKLOADS3, cores, length=LEN, seed=7)
    got = simulate_batch(ndp_machine(cores), traces, chunk=512)
    want = ref.simulate(machine, cfg["mechanisms"], traces, 512)
    assert len(got) == len(want) == len(traces)
    for w, g, r in zip(WORKLOADS3, got, want):
        for k in ref.COUNTERS:
            np.testing.assert_array_equal(getattr(g, k), r[k],
                                          err_msg=f"{w}: {k}")
        for k in ref.CYCLES:
            np.testing.assert_allclose(getattr(g, k), r[k],
                                       rtol=S.CYCLES_RTOL,
                                       err_msg=f"{w}: {k}")


class TestSharding:
    @pytest.mark.skipif(len(jax.devices()) < 2,
                        reason="needs >1 XLA host device (SIM_DEVICES)")
    def test_sharded_equals_unsharded(self):
        mach = ndp_machine(2)
        traces = generate_traces(WORKLOADS3, 2, length=LEN, seed=7)
        sharded = simulate_batch(mach, traces, chunk=512,
                                 devices=len(jax.devices()))
        unsharded = simulate_batch(mach, traces, chunk=512, devices=1)
        for s, u in zip(sharded, unsharded):
            _assert_results_equal(s, u, msg="sharded")

    @pytest.mark.slow
    def test_sharded_equals_unsharded_subprocess(self):
        """Force 2 host devices in a fresh process (the in-process test
        above is skipped on default single-device runs)."""
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=2"
                            ).strip()
        env["SIM_DEVICES"] = "2"
        env["PYTHONPATH"] = os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", "src"))
        code = (
            "import jax, numpy as np\n"
            "assert len(jax.devices()) == 2, jax.devices()\n"
            "from repro.configs.ndp_sim import ndp_machine\n"
            "from repro.sim import simulate_batch\n"
            "from repro.workloads import generate_traces\n"
            "traces = generate_traces(('rnd', 'bc', 'bfs'), 2,"
            " length=700, seed=7)\n"
            "mach = ndp_machine(2)\n"
            "sh = simulate_batch(mach, traces, chunk=512, devices=2)\n"
            "un = simulate_batch(mach, traces, chunk=512, devices=1)\n"
            "for s, u in zip(sh, un):\n"
            "    np.testing.assert_array_equal(s.cycles, u.cycles)\n"
            "    np.testing.assert_array_equal(s.walks, u.walks)\n"
            "print('SHARD_OK')\n"
        )
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=560)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "SHARD_OK" in out.stdout


class TestSelect:
    @pytest.fixture(scope="class")
    def res(self):
        mach = ndp_machine(2)
        traces = generate_traces(("rnd",), 2, length=LEN, seed=7)
        return simulate_batch(mach, traces, chunk=512)[0]

    def test_select_mechs_subset_and_order(self, res):
        sub = res.select(mechs=("ndpage", "radix"))
        assert sub.mechs == ("ndpage", "radix")
        np.testing.assert_array_equal(
            sub.cycles[1], res.cycles[res.mechs.index("radix")])

    def test_select_cores(self, res):
        one = res.select(cores=1)
        assert one.cycles.shape == (len(res.mechs), 1)
        np.testing.assert_array_equal(one.instructions,
                                      res.instructions[1:2])
        sl = res.select(cores=slice(0, 2))
        np.testing.assert_array_equal(sl.cycles, res.cycles)

    def test_scalar_matches_raw_indexing(self, res):
        i = res.mechs.index("radix")
        want = float((res.walk_cycles[i] /
                      np.maximum(res.walks[i], 1)).mean())
        assert res.scalar("avg_ptw_latency", "radix") == pytest.approx(want)

    def test_derived_metrics_survive_selection(self, res):
        sub = res.select(mechs=("radix", "ideal"))
        assert sub.speedup_vs("radix")["ideal"] == pytest.approx(
            res.speedup_vs("radix")["ideal"])

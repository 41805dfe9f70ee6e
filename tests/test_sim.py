"""Simulator unit + behaviour tests (LRU lookup, mechanisms ordering)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.ndp_sim import cpu_machine, ndp_machine
from repro.sim import simulate
from repro.sim.simulator import lru_access
from repro.workloads import generate_trace

T = jnp.asarray(True)
F = jnp.asarray(False)


def key(x):
    return jnp.asarray(x, jnp.int32)


def table(sets, ways):
    """One (mech, core)'s LRU table as init_state lays it out."""
    return {"tags": jnp.zeros((sets, ways), jnp.int32),
            "lru": jnp.zeros((sets, ways), jnp.int32)}


def run(tab, keys, en=T, stamp=1):
    """Access ``keys`` in order with rising stamps; returns the table,
    the hit bits and the next stamp."""
    sets = tab["tags"].shape[0]
    hits = []
    for k in keys:
        tab, hit = lru_access(tab, sets, key(k), en, key(stamp))
        hits.append(bool(hit))
        stamp += 1
    return tab, hits, stamp


class TestCacheModel:
    """The scan's one LRU lookup, :func:`lru_access`: an enabled access
    always fills, so a probe is an access too."""

    def test_miss_then_hit(self):
        _, hits, _ = run(table(4, 2), (5, 5))
        assert hits == [False, True]

    def test_lru_eviction(self):
        st = table(1, 2)  # fully assoc, 2 ways
        st, _, n = run(st, (1, 2))
        st, _, n = run(st, (1,), stamp=n)     # 1 is MRU
        st, _, n = run(st, (3,), stamp=n)     # evicts 2
        # probing 1 refills it as MRU, so 1 is probed before 2
        _, (hit1, hit2), _ = run(st, (1, 2), stamp=n)
        assert hit1 and not hit2

    def test_disabled_access_is_invisible(self):
        st = table(4, 2)
        st2, hits, _ = run(st, (9,), en=F)
        assert hits == [False]
        assert (st2["tags"] == st["tags"]).all()
        assert (st2["lru"] == st["lru"]).all()

    def test_set_isolation(self):
        st = table(4, 1)
        st, _, n = run(st, (0, 1))            # set 0, then set 1
        _, hits, _ = run(st, (0,), stamp=n)
        assert hits == [True]


class TestSimulator:
    """Behavioural checks on the smoke preset — the same engine code path
    as full runs (chunked scan, spec registry) at CI-compatible cost."""

    @pytest.fixture(scope="class")
    def result(self, smoke_sim):
        return smoke_sim("rnd", ndp_machine(2))

    def test_ideal_is_fastest(self, result):
        sp = result.speedup_vs()
        assert sp["ideal"] >= max(v for k, v in sp.items() if k != "ideal")

    def test_ndpage_beats_radix_on_ndp(self, result):
        assert result.speedup_vs()["ndpage"] > 1.05

    def test_ndpage_walk_shorter_than_radix(self, result):
        ptw = result.avg_ptw_latency()
        assert ptw[3] < ptw[0]          # ndpage < radix
        assert ptw[4] == 0              # ideal never walks

    def test_pte_l1_missrate_high_on_ndp(self, result):
        # Observation A: PTE accesses can't use the small NDP L1
        assert result.pte_l1_miss_rate()[0] > 0.7

    def test_counters_consistent(self, result):
        assert (result.walks <= result.l1tlb_misses + 1e-6).all()
        assert (result.trans_cycles <= result.cycles).all()

    def test_cpu_less_translation_bound_than_ndp(self, smoke_sim):
        ndp = smoke_sim("bfs", ndp_machine(2))
        cpu = smoke_sim("bfs", cpu_machine(2))
        assert (cpu.translation_fraction()[0]
                < ndp.translation_fraction()[0])

    def test_chunk_padding_invariance(self, smoke):
        # a padded single-chunk run must match an exact-fit single-chunk
        # run entry for entry (both see one queue window, so the only
        # difference is the padding mask)
        trace = generate_trace("rnd", 1, 700, seed=3, preset=smoke)
        exact = simulate(ndp_machine(1), trace, chunk=700)
        padded = simulate(ndp_machine(1), trace, chunk=1024)
        np.testing.assert_allclose(exact.cycles, padded.cycles, rtol=1e-6)
        np.testing.assert_array_equal(exact.walks, padded.walks)
        assert exact.accesses == padded.accesses == 700

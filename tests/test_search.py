"""Design-space search: seeded determinism, Pareto/dominance laws,
the 1-candidate == sweep() bit-exactness bridge, the compile-count
bound over a full generation, and the BENCH/baseline plumbing.

Chunk lengths 416/448 are unique to this file so runner-cache compile
accounting is exact (the cache is keyed on (shape, walk fns, chunk)
and shared process-wide).
"""
import itertools
import json
import os
import sys

import numpy as np
import pytest

from repro.sim._search import (OBJECTIVES, SearchSpace, build_machine,
                              dominates, evaluate_genomes, mech_for,
                              merge_search_section, paper_genome,
                              pareto_indices, search, sram_kb)
from repro.sim import sweep

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks import sim_search  # noqa: E402

CHUNK = 416
CHUNK_FRESH = 448      # compile-bound test only: fresh runner keys
LEN = 416


def _space(**over):
    base = dict(
        name="tiny",
        knobs=(("pwc_entries", (16, 32)),
               ("flatten", ("pl2", "pl3")),
               ("l1_bypass", (True, False))),
        cores=2, workloads=("rnd", "xs"),
        n_random=5, population=8, generations=2, offspring=4,
        trace_len=LEN, chunk=CHUNK, preset="smoke", seed=11)
    base.update(over)
    return SearchSpace(**base)


@pytest.fixture(scope="module")
def res():
    """One search over the tiny space, shared by the read-only tests."""
    return search(_space(), use_cache=False)


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------
def test_same_seed_bit_identical_frontier(res):
    """The same seed over the same space must reproduce the frontier
    bit-for-bit: same genomes, same order, same objective floats."""
    again = search(_space(), use_cache=False)
    assert [dict(c.genome) for c in again.frontier] == \
           [dict(c.genome) for c in res.frontier]
    assert [c.objectives for c in again.frontier] == \
           [c.objectives for c in res.frontier]
    assert [dict(c.genome) for c in again.candidates] == \
           [dict(c.genome) for c in res.candidates]
    assert again.verdict["dominates_paper"] == \
        res.verdict["dominates_paper"]
    assert again.provenance["evaluated"] == res.provenance["evaluated"]


def test_provenance_and_verdict(res):
    p = res.provenance
    assert p["seed"] == 11
    assert p["evaluated"] == len(res.candidates) >= 6
    # no-recompile invariant: compiles bounded by the distinct
    # (machine-shape x walk-fn) buckets, never the candidate count
    assert p["runner_compiles"] <= p["distinct_buckets"]
    assert isinstance(res.verdict["dominates_paper"], bool)
    assert res.paper.origin == "paper"
    assert dict(res.paper.genome) == dict(
        zip(("pwc_entries", "flatten", "l1_bypass"), (32, "pl2", True)))


# ---------------------------------------------------------------------------
# 1-candidate search == direct sweep() point
# ---------------------------------------------------------------------------
def test_single_candidate_bit_exact_vs_sweep():
    """A degenerate 1-genome search (the paper point, whose geometry IS
    the ndp default machine) must reproduce a direct sweep() over the
    same workloads counter-for-counter."""
    space = _space(knobs=(("pwc_entries", (32,)),),
                   n_random=0, generations=0, offspring=0)
    r = search(space, use_cache=False)
    assert len(r.candidates) == 1
    cand = r.candidates[0]
    assert cand.mech == "ndpage"

    sw = sweep({"workload": space.workloads}, cores=space.cores,
               mechs=("radix", "ndpage"), trace_len=LEN, chunk=CHUNK)
    worst = -np.inf
    for wl in space.workloads:
        pt = sw.point(workload=wl)
        assert pt.speedup_vs("radix")["ndpage"] == \
            cand.per_workload[wl], wl
        worst = max(worst, pt.scalar("avg_ptw_latency", "ndpage"))
    assert cand.objectives["worst_ptw"] == worst
    assert cand.objectives["mean_speedup"] == float(
        np.mean(list(cand.per_workload.values())))
    assert cand.objectives["sram_kb"] == sram_kb(
        space, paper_genome(space))


# ---------------------------------------------------------------------------
# dominance / frontier laws (seeded random objective vectors)
# ---------------------------------------------------------------------------
def test_frontier_contains_no_dominated_points():
    rng = np.random.default_rng(0)
    names = [n for n, _ in OBJECTIVES]
    for _ in range(25):
        vecs = [dict(zip(names, row))
                for row in rng.random((rng.integers(1, 20), 3))]
        front = set(pareto_indices(vecs))
        assert front, "frontier can never be empty"
        for i, v in enumerate(vecs):
            dominated = any(dominates(w, v)
                            for j, w in enumerate(vecs) if j != i)
            assert (i in front) == (not dominated)
        # dominance is irreflexive and asymmetric
        for v in vecs:
            assert not dominates(v, v)
        for a in vecs:
            for b in vecs:
                assert not (dominates(a, b) and dominates(b, a))


def test_search_frontier_is_nondominated(res):
    vecs = [c.objectives for c in res.frontier]
    assert pareto_indices(vecs) == list(range(len(vecs)))
    # and every non-frontier candidate is dominated by some frontier pt
    for c in res.candidates:
        if c.objectives in vecs:
            continue
        assert any(dominates(f.objectives, c.objectives)
                   for f in res.frontier), c.genome


# ---------------------------------------------------------------------------
# compile bound across a full >= 24-candidate generation
# ---------------------------------------------------------------------------
def test_generation_compile_count_bounded_by_buckets():
    """24 candidates spanning 3 machine shapes x 8 mechanism structures
    dispatch as exactly (shape x walk-fn-tuple) buckets: 6 runner
    compiles, not 24."""
    space = _space(knobs=(("pwc_entries", (8, 16, 32)),
                          ("flatten", ("pl2", "pl3")),
                          ("l1_bypass", (True, False)),
                          ("huge", (False, True))),
                   workloads=("rnd",), chunk=CHUNK_FRESH)
    genomes = [tuple(g) for g in itertools.product(
        (8, 16, 32), ("pl2", "pl3"), (True, False), (False, True))]
    assert len(genomes) == 24
    evals, stats = evaluate_genomes(space, genomes)
    assert len(evals) == 24
    assert stats["points"] == 24                    # one workload each
    # bypass/huge are value-only lane data; only (pwc shape x flatten
    # walk-fn) forces a bucket -> 3 shapes x 2 walk fns
    assert stats["buckets"] == 6
    assert stats["distinct_shapes"] == 3
    assert stats["runner_compiles"] == 6
    # every structural combo really got its own mechanism variant
    assert len({mech_for(space, g) for g in genomes}) == 8


def test_geometry_knobs_reach_the_machine():
    space = _space(knobs=(("pwc_entries", (16, 32)),
                          ("l1_dtlb", ((64, 4), (128, 8))),
                          ("l2_tlb.entries", (1536, 3072))))
    g = (16, (128, 8), 3072)
    mach = build_machine(space, g)
    assert mach.pwc_entries == 16
    assert (mach.l1_dtlb.entries, mach.l1_dtlb.ways) == (128, 8)
    assert mach.l2_tlb.entries == 3072
    assert sram_kb(space, g) == (16 * 4 * 8 + 128 * 8 + 3072 * 8) / 1024


# ---------------------------------------------------------------------------
# BENCH_sim.json merge + frontier baseline gate
# ---------------------------------------------------------------------------
def test_merge_never_clobbers_other_sections(tmp_path):
    path = str(tmp_path / "BENCH_sim.json")
    with open(path, "w") as f:
        json.dump({"figures_wall_s": 1.0, "sweeps": {"pwc_size": {}},
                   "serving": {"x": 1}}, f)
    merge_search_section({"frontier": []}, path)
    with open(path) as f:
        data = json.load(f)
    assert data["figures_wall_s"] == 1.0
    assert data["sweeps"] == {"pwc_size": {}}
    assert data["serving"] == {"x": 1}
    assert data["search"] == {"frontier": []}


def test_frontier_baseline_roundtrip(res, tmp_path):
    """Pinning the discovered frontier and re-checking it passes; a
    baseline pinning a dominated genome fails the gate."""
    path = str(tmp_path / "frontier_baseline.json")
    sim_search.update_baseline(res, path)
    ok, note = sim_search.check_frontier_baseline(res, path)
    assert ok, note

    dominated = [c for c in res.candidates
                 if any(dominates(f.objectives, c.objectives)
                        for f in res.frontier)]
    if not dominated:
        pytest.skip("tiny space produced no dominated candidate")
    with open(path) as f:
        base = json.load(f)
    base["points"] = [dominated[0].to_json_dict()]
    with open(path, "w") as f:
        json.dump(base, f)
    ok, note = sim_search.check_frontier_baseline(res, path)
    assert not ok and "dominated" in note

    # and a missing baseline is a skip, not a failure
    ok, note = sim_search.check_frontier_baseline(
        res, str(tmp_path / "absent.json"))
    assert ok and "no baseline" in note


def test_eval_cache_reuse(tmp_path, monkeypatch):
    """A warm on-disk eval cache must reproduce the frontier without a
    single new simulation lane."""
    monkeypatch.setenv("SIM_TRACE_CACHE", str(tmp_path / "cache"))
    space = _space(n_random=2, generations=1, offspring=2)
    cold = search(space, use_cache=True)
    warm = search(space, use_cache=True)
    assert warm.provenance["lanes_dispatched"] == 0
    assert warm.provenance["eval_cache_hits"] > 0
    assert [c.objectives for c in warm.frontier] == \
           [c.objectives for c in cold.frontier]

"""MemoryModel: spec semantics, bounded_linear bit-exactness vs the
pre-MemoryModel engine, banked row-buffer locality, per-bank queue
independence, the shape/data split, and the legacy-kwarg shim."""
import dataclasses
import warnings

import numpy as np
import pytest

from repro.configs.ndp_sim import MachineConfig, cpu_machine, ndp_machine
from repro.core import page_table as PT
from repro.sim import (MEMORY_MODELS, MemoryModel, apply_param, simulate,
                       simulate_batch, sweep)
from repro.sim import memory_model as mm
from repro.sim.simulator import CYCLES_RTOL
from repro.workloads import generate_trace

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # pragma: no cover - CI has it
    HAVE_HYPOTHESIS = False


def banked(mach: MachineConfig) -> MachineConfig:
    """The machine with its memory switched to the banked preset
    (calibration-preserving, same as the sweep knob)."""
    return apply_param(mach, "memory_model", "banked")


def _assert_results_equal(a, b, msg=""):
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if isinstance(va, np.ndarray):
            np.testing.assert_array_equal(va, vb,
                                          err_msg=f"{msg}: {f.name}")
        else:
            assert va == vb, f"{msg}: {f.name}"


# ---------------------------------------------------------------------------
# the spec itself
# ---------------------------------------------------------------------------
class TestSpec:
    def test_presets_and_derived_timings(self):
        bl = MEMORY_MODELS["bounded_linear"]
        bk = MEMORY_MODELS["banked"]
        assert bl.miss_latency() == bl.hit_latency() == bl.latency
        assert bl.row_hit_save() == 0.0
        assert bk.miss_latency() == (bk.overhead + bk.t_rp + bk.t_rcd
                                     + bk.t_cas)
        assert bk.hit_latency() == bk.overhead + bk.t_cas
        assert bk.row_hit_save() == bk.t_rp + bk.t_rcd
        # the banked ndp preset is calibrated to the bounded ndp latency
        assert bk.miss_latency() == bk.latency == 100.0

    def test_line_cycles_prices_contiguity(self):
        bk = MEMORY_MODELS["banked"]
        assert bk.line_cycles(contiguous=True) == bk.hit_latency()
        assert bk.line_cycles(contiguous=False) == bk.miss_latency()
        bl = MEMORY_MODELS["bounded_linear"]
        assert (bl.line_cycles(True) == bl.line_cycles(False)
                == bl.latency)

    def test_shape_key_splits_only_on_geometry(self):
        bl = MEMORY_MODELS["bounded_linear"]
        assert bl.shape_key() == ("bounded_linear",)
        # timings are DATA: same shape key
        assert (dataclasses.replace(bl, latency=60.0).shape_key()
                == bl.shape_key())
        bk = MEMORY_MODELS["banked"]
        assert bk.shape_key() == ("banked", 16, 2048)
        assert (dataclasses.replace(bk, t_cas=40.0).shape_key()
                == bk.shape_key())
        assert (dataclasses.replace(bk, num_banks=8).shape_key()
                != bk.shape_key())

    def test_validation(self):
        with pytest.raises(ValueError, match="unknown memory model kind"):
            MemoryModel(kind="open_page")
        with pytest.raises(ValueError, match="num_banks"):
            MemoryModel(kind="banked", num_banks=0)
        with pytest.raises(ValueError, match="row_buffer_bytes"):
            MemoryModel(kind="banked", row_buffer_bytes=100)
        with pytest.raises(ValueError, match="t_cas"):
            MemoryModel(kind="banked", t_cas=-1.0)

    def test_resolve(self):
        assert mm.resolve_memory_model(None) is MEMORY_MODELS[
            "bounded_linear"]
        assert mm.resolve_memory_model("banked") is MEMORY_MODELS["banked"]
        got = mm.resolve_memory_model(dict(latency=60.0))
        assert got.latency == 60.0 and got.kind == "bounded_linear"
        with pytest.raises(KeyError, match="unknown memory model preset"):
            mm.resolve_memory_model("ddr9")
        with pytest.raises(TypeError):
            mm.resolve_memory_model(42)

    def test_with_kind_preserves_calibration(self):
        cpu = cpu_machine(1).memory          # latency 170, bounded
        bk = mm.with_kind(cpu, "banked")
        assert bk.kind == "banked"
        # closed-row total re-calibrated to the cpu's access latency
        assert bk.miss_latency() == pytest.approx(170.0)
        back = mm.with_kind(bk, "bounded_linear")
        assert back.kind == "bounded_linear"
        assert back.latency == 170.0
        # service carries from the CURRENT model (the per-bank service
        # is real calibration too); the no-op switch is a true identity
        assert back.service == bk.service
        assert mm.with_kind(cpu, "bounded_linear") == cpu


# ---------------------------------------------------------------------------
# MachineConfig integration + the legacy shim
# ---------------------------------------------------------------------------
class TestMachineConfig:
    def test_factories_carry_memory_models(self):
        assert ndp_machine(2).memory.latency == 100.0
        assert cpu_machine(2).memory.latency == 170.0
        assert ndp_machine(2).memory.kind == "bounded_linear"

    def test_deprecated_properties_read_through(self):
        mach = ndp_machine(2)
        assert mach.mem_latency == mach.memory.latency
        assert mach.mem_bandwidth_gbs == mach.memory.bandwidth_gbs
        assert mach.mem_service == mach.memory.service

    def test_legacy_kwargs_warn_once_and_fold_into_memory(self):
        mm._WARNED_LEGACY = False
        base = ndp_machine(2)
        with pytest.warns(DeprecationWarning, match="memory"):
            legacy = dataclasses.replace(base, mem_latency=123.0,
                                         mem_service=40.0)
        assert legacy.memory.latency == 123.0
        assert legacy.memory.service == 40.0
        assert legacy.memory.kind == "bounded_linear"
        # second use: silent (one warning per process)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            again = dataclasses.replace(base, mem_latency=60.0)
        assert again.memory.latency == 60.0

    def test_legacy_sweep_path_rewrites(self):
        mm._WARNED_LEGACY = False
        with pytest.warns(DeprecationWarning):
            m = apply_param(ndp_machine(2), "mem_latency", 60.0)
        assert m.memory.latency == 60.0

    def test_unknown_memory_knob_lists_known_knobs(self):
        with pytest.raises(ValueError, match="known knobs are"):
            apply_param(ndp_machine(2), "memory.t_casz", 10.0)
        with pytest.raises(ValueError, match="memory_model"):
            apply_param(ndp_machine(2), "memory.kindz", "banked")
        # nested VALUE overrides still flow through
        m = apply_param(ndp_machine(2), "memory.t_cas", 40.0)
        assert m.memory.t_cas == 40.0

    def test_memory_model_knob_switches_kind(self):
        m = banked(cpu_machine(2))
        assert m.memory.kind == "banked"
        assert m.memory.miss_latency() == pytest.approx(170.0)


# ---------------------------------------------------------------------------
# bounded_linear is bit-exact vs the pre-MemoryModel engine
# ---------------------------------------------------------------------------
#: pinned per-mechanism mean cycles of the default bounded engine,
#: captured on the commit that introduced MemoryModel.  Cycles are f32
#: sums whose association is the backend's choice, so they are compared
#: at CYCLES_RTOL (its definition in repro.sim.simulator derives the
#: bound); the integer-valued counters below are order-free and exact.
PIN_NDP8_RND = [1833050.75, 1702481.0, 2007893.75, 1330220.75,
                651822.0625]
PIN_NDP8_SUMS = (60203748.0, 34130864.0, 31640676.0)
PIN_CPU4_BC = [390846.4375, 351464.78125, 404215.375, 299769.90625,
               169400.359375]
PIN_CPU4_SUM = 6462787.5
#: per-mechanism counter totals over cores (radix, ech, hugepage,
#: ndpage, ideal)
PIN_NDP8_COUNTS = dict(
    walks=[16376, 16376, 16197, 16376, 0],
    l1tlb_misses=[16383, 16383, 16372, 16383, 0],
    pte_accesses=[31976, 32752, 30817, 16400, 0],
    pte_l1_hits=[3104, 0, 2847, 0, 0],
    pte_mem=[28872, 32752, 27970, 16400, 0],
    data_l1_misses=[16384] * 5,
    data_mem=[16384] * 5)
PIN_CPU4_COUNTS = dict(
    walks=[2596, 2596, 2135, 2596, 0],
    l1tlb_misses=[2637, 2637, 2301, 2637, 0],
    pte_accesses=[4513, 5192, 3064, 2604, 0],
    pte_l1_hits=[598, 0, 316, 0, 0],
    pte_mem=[3532, 5192, 2535, 2604, 0],
    data_l1_misses=[2829, 2828, 2828, 2821, 2821],
    data_mem=[2821] * 5)


def _assert_counts(res, pinned):
    for name, want in pinned.items():
        np.testing.assert_array_equal(getattr(res, name).sum(axis=1),
                                      np.array(want, np.float32),
                                      err_msg=name)


class TestBoundedBitExact:
    def test_ndp_pinned(self):
        res = simulate(ndp_machine(8),
                       generate_trace("rnd", 8, length=2048, seed=1234,
                                      preset="smoke"), chunk=512)
        _assert_counts(res, PIN_NDP8_COUNTS)
        np.testing.assert_allclose(res.cycles.mean(axis=1),
                                   PIN_NDP8_RND, rtol=CYCLES_RTOL)
        np.testing.assert_allclose(
            [res.cycles.sum(), res.trans_cycles.sum(),
             res.walk_cycles.sum()], PIN_NDP8_SUMS, rtol=CYCLES_RTOL)

    def test_cpu_pinned(self):
        res = simulate(cpu_machine(4),
                       generate_trace("bc", 4, length=1024, seed=7,
                                      preset="smoke"), chunk=256)
        _assert_counts(res, PIN_CPU4_COUNTS)
        np.testing.assert_allclose(res.cycles.mean(axis=1),
                                   PIN_CPU4_BC, rtol=CYCLES_RTOL)
        np.testing.assert_allclose(res.cycles.sum(), PIN_CPU4_SUM,
                                   rtol=CYCLES_RTOL)


# ---------------------------------------------------------------------------
# row-buffer locality at the address-mapping level
# ---------------------------------------------------------------------------
def _row_hit_fraction(lines: np.ndarray, model: MemoryModel) -> float:
    """Fraction of accesses that find their bank's row open, replaying
    ``lines`` in order against per-bank last-row state — the numpy twin
    of the engine's carried ``bank_row`` tables."""
    banks = np.asarray(mm.bank_of(lines, model.num_banks,
                                  model.lines_per_row))
    rows = np.asarray(mm.row_of(lines, model.num_banks,
                                model.lines_per_row))
    open_row = {}
    hits = 0
    for b, r in zip(banks.tolist(), rows.tolist()):
        hits += open_row.get(b) == r
        open_row[b] = r
    return hits / len(banks)


class TestRowBufferLocality:
    def test_flat_span_walk_hits_radix_node_allocations_miss(self):
        # the structural claim at allocation granularity: the flat
        # table's leaf span is ONE contiguous line run, so walking it
        # streams through open rows; the radix tree allocates each leaf
        # node independently (hash-scattered bases), so stepping from
        # node to node lands on a fresh row every time
        model = MEMORY_MODELS["banked"]
        span_vpns = np.arange(0, 1 << 15, 8, dtype=np.int64)
        flat = np.asarray(PT.ndpage_walk_lines(span_vpns))[:, -1]
        assert (np.diff(flat) == 1).all()    # one contiguous run
        node_vpns = np.arange(256, dtype=np.int64) * 512
        radix = np.asarray(PT.radix4_walk_lines(node_vpns))[:, -1]
        f_flat = _row_hit_fraction(flat, model)
        f_radix = _row_hit_fraction(radix, model)
        assert f_flat > 0.9, f_flat
        assert f_radix < 0.1, f_radix

    def test_mapping_round_trip(self):
        model = MEMORY_MODELS["banked"]
        lines = np.arange(10 * model.num_banks * model.lines_per_row)
        banks = mm.bank_of(lines, model.num_banks, model.lines_per_row)
        rows = mm.row_of(lines, model.num_banks, model.lines_per_row)
        # every (bank, row) pair holds exactly lines_per_row lines
        pair = banks * (rows.max() + 1) + rows
        _, counts = np.unique(pair, return_counts=True)
        assert (counts == model.lines_per_row).all()

    def test_row_hits_save_cycles_end_to_end(self):
        # neutralize ONLY the row-hit save (t_rp = t_rcd = 0, overhead
        # bumped so the closed-row total stays 100 cycles): the machine
        # with the save enabled must never be slower, and strictly
        # faster for ndpage — proof the engine's carried bank_row state
        # actually fires on the flat-leaf/data line streams.  All
        # value-only: both runs share one compiled runner.
        mach = banked(ndp_machine(2))
        nosave = mach
        for p, v in (("memory.t_rp", 0.0), ("memory.t_rcd", 0.0),
                     ("memory.overhead", 75.0)):
            nosave = apply_param(nosave, p, v)
        assert (nosave.memory.miss_latency()
                == mach.memory.miss_latency() == 100.0)
        tr = generate_trace("xs", 2, length=512, seed=3, preset="smoke")
        with_save = simulate(mach, tr, chunk=512)
        without = simulate(nosave, tr, chunk=512)
        diff = without.cycles.mean(axis=1) - with_save.cycles.mean(axis=1)
        assert (diff >= 0.0).all(), diff
        assert diff[with_save.mechs.index("ndpage")] > 0.0, diff


# ---------------------------------------------------------------------------
# per-bank queue independence
# ---------------------------------------------------------------------------
class TestBankQueue:
    def test_bank_queues_are_independent(self):
        # per-(mech, bank) rates: perturbing bank 0's load must leave
        # every other bank's queue delay bit-identical
        rate = np.full((3, 8), 1.0 / 200.0)
        base = np.asarray(mm.queue_delay(rate, 117.0))
        hot = rate.copy()
        hot[:, 0] *= 10.0
        after = np.asarray(mm.queue_delay(hot, 117.0))
        assert (after[:, 0] >= base[:, 0]).all()
        np.testing.assert_array_equal(after[:, 1:], base[:, 1:])

    def test_queue_delay_saturates(self):
        lo = float(np.asarray(mm.queue_delay(1e-9, 100.0)))
        hi = float(np.asarray(mm.queue_delay(1e9, 100.0)))
        assert lo == pytest.approx(0.0, abs=1e-3)
        assert hi == pytest.approx(100.0 * mm.RHO_MAX * mm.QUEUE_K)


# ---------------------------------------------------------------------------
# shape/data split + batch bit-exactness (the sweep engine contract)
# ---------------------------------------------------------------------------
class TestShapeDataSplit:
    # chunks no other test uses: the runner cache entries are cold, so
    # compile counts are attributable to THIS grid
    CHUNK_TIMING = 416
    CHUNK_BANKS = 448

    def test_timing_sweep_is_one_bucket_one_compile(self):
        r = sweep({"memory_model": ("banked",),
                   "memory.t_cas": (15.0, 40.0),
                   "memory.t_rp": (20.0, 30.0),
                   "workload": ("rnd",)},
                  base="ndp", cores=2, trace_len=320,
                  chunk=self.CHUNK_TIMING)
        assert r.stats["buckets"] == 1
        assert r.stats["runner_compiles"] == 1
        # and t_cas actually moved the numbers (the lanes are not
        # accidentally aliased)
        cyc = r.map(lambda x: float(x.cycles.sum()))
        assert (np.diff(cyc, axis=1) > 0).all()

    def test_bank_geometry_is_shape(self):
        r = sweep({"memory_model": ("banked",),
                   "memory.num_banks": (8, 16),
                   "workload": ("rnd",)},
                  base="ndp", cores=2, trace_len=320,
                  chunk=self.CHUNK_BANKS)
        assert r.stats["buckets"] == 2
        assert r.stats["runner_compiles"] == 2

    def test_banked_single_vs_batch_bit_exact(self):
        mach = banked(ndp_machine(2))
        traces = [generate_trace(w, 2, length=700, seed=7, preset="smoke")
                  for w in ("rnd", "bc")]
        singles = [simulate(mach, tr, chunk=512) for tr in traces]
        batched = simulate_batch(mach, traces, chunk=512)
        for s, b in zip(singles, batched):
            _assert_results_equal(s, b, msg="banked batch")


# ---------------------------------------------------------------------------
# total latency is monotone in t_cas
# ---------------------------------------------------------------------------
def _banked_cycles(t_cas: float) -> float:
    # ONE chunk (trace_len == chunk): no cross-chunk queue feedback, so
    # monotonicity in t_cas is strict, not just statistical
    mach = apply_param(banked(ndp_machine(2)), "memory.t_cas",
                       float(t_cas))
    tr = generate_trace("rnd", 2, length=256, seed=11, preset="smoke")
    return float(simulate(mach, tr, chunk=256).cycles.sum())


class TestMonotoneInTcas:
    @pytest.mark.parametrize("lo,hi", [(5.0, 25.0), (25.0, 60.0)])
    def test_monotone_fixed_points(self, lo, hi):
        assert _banked_cycles(lo) < _banked_cycles(hi)

    if HAVE_HYPOTHESIS:
        @settings(max_examples=10, deadline=None)
        @given(lo=st.floats(1.0, 80.0), delta=st.floats(0.5, 40.0))
        def test_monotone_property(self, lo, delta):
            # t_cas is value-only data: every example reuses ONE
            # compiled runner
            assert _banked_cycles(lo) <= _banked_cycles(lo + delta)

"""Trace-driven timing simulator for NDP/CPU address translation.

Mechanistic interval model (Sniper-style): every trace entry is one memory
instruction preceded by ``work`` non-memory instructions.  Per entry we
model, for all mechanisms at once (leading M axis) and all cores (C axis):

  1. L1 DTLB lookup (free on hit) -> L2 TLB (12cy) -> page-table walk
  2. the walk's PTE accesses: per-level PWC, then cache hierarchy or —
     for NDPage — a direct memory access (L1 bypass), serial for
     radix/hugepage/ndpage, parallel (max) for ECH
  3. the data access through the cache hierarchy
  4. a shared-memory queueing delay from aggregate measured demand
     (bounded-linear: q = service * rho * K, rho from running totals)

PTE fills pollute the caches for radix/ECH/hugepage; NDPage bypasses; Ideal
performs no translation at all.  Huge pages use scaled-huge TLB keys and a
fragmentation model (4KB-fallback fraction grows with core count — the
contiguity-exhaustion effect the paper describes for 8 cores).

Which mechanisms run, and their static structure (walk depth, parallel
probes, L1 bypass, PWC placement, huge-page semantics), comes from the
declarative spec registry in :mod:`repro.sim.mechanisms` — adding a
mechanism there is all it takes to simulate it.

Engine
------
ONE jitted chunk runner (:func:`_chunk_runner`), a chunked
``jax.lax.scan`` split along the only real serial dependency:

* the **scan** carries nothing but the LRU tag/stamp tables and performs
  the cache/TLB/PWC lookups (:func:`lru_access`, the state evolution that
  must be sequential), emitting one packed int32 of hit bits per (mech,
  lane) per entry;
* a vectorized **epilogue** (same jit) expands the hit bits over the
  whole chunk at once and does every latency/counter computation there —
  the per-step graph stays tiny, which is what per-op-overhead-bound CPU
  backends need.

The trace is pre-generated, padded to fixed-shape chunks, and streamed
through the runner, whose state buffers are donated between chunks.
The runner is compiled once per (machine SHAPE, mechanism walk-fn tuple,
chunk length) — trace length never retriggers compilation, and neither
does any value-like machine parameter: :class:`MachineShape` captures
only what determines array shapes (core count, table geometries), while
latencies/service times (:func:`_data_params`) and the per-mechanism
flag tables (:func:`_mech_arrays`) enter the jit as plain operands.
That split is what makes parameter sweeps cheap — a grid over memory
latency or the L1-bypass flag reuses one compiled runner, with the
varying values riding the batch lanes as data (see
:mod:`repro.sim._sweep`).  The queueing delay is held constant within a
chunk (recomputed from aggregate demand at every chunk boundary), which
is what makes the split exact.

Batch axis
----------
Every run is a batch of B *independent simulations* that share one
``MachineConfig`` shape (e.g. all Table-II workloads at a given machine
× core count): :func:`simulate_batch` runs the whole bucket as ONE
chunked-scan dispatch, and :func:`simulate` is the batch of one.  LRU
tables are laid out ``(B, C, M, sets, ways)`` — every mapped axis stays
leading, so no per-step transpose is ever materialized — and are
reshaped (free: the leading axes are contiguous) onto the fused
``(B*C, M, sets, ways)`` lane layout at dispatch: two vmap levels (lanes
× mechanisms) over a wide lane axis, which XLA-CPU runs at full width,
whereas a literal third vmap level regresses the per-step gathers ~2x.
Per-sim queue windows, valid masks (lanes may have different true trace
lengths), and counters stay per-sim and are sliced back into per-sim
:class:`SimResult` objects at the end; lanes never interact, so a
lane's result does not depend on its neighbours or its place in the
batch.  When more than one XLA host device is available (opt-in via
``SIM_DEVICES=N`` before process start, which forces
``--xla_force_host_platform_device_count``), the B axis is sharded
across devices with ``jax.sharding`` — lanes never communicate, so the
fleet parallelizes embarrassingly.

:func:`simulate_batch_varied` generalizes the lanes to heterogeneous
jobs: every lane carries its own ``MachineConfig`` *values* and its own
mechanism-table *values* (the shape half must match — that is the
bucket invariant the sweep engine enforces), so one dispatch can cover
a whole sensitivity grid over latencies, bypass flags, or huge-page
knobs.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.ndp_sim import MachineConfig
from repro.core import page_table as PT
from repro.sim import mechanisms as _mechanisms
from repro.sim import memory_model as MM
from repro.sim.mechanisms import (DEFAULT_MECHS, MAX_PTE, specs_for,
                                  tables_for)
from repro.util import spans

MECHS = DEFAULT_MECHS

#: scan-chunk length; traces are padded to a multiple of this so one
#: compiled runner serves every trace length
DEFAULT_CHUNK = 512

# 2MB huge pages: 512 x 4KB pages (footprints are unscaled)
HUGE_SHIFT = 9

# huge-page cost model (the effects the paper attributes to huge pages:
# "increased page fault latency, bloat memory footprint, and rapid
# consumption of available physical memory contiguity"):
#  - FRAC_4K: fraction of memory falling back to 4KB mappings as
#    contiguity is consumed (grows with allocating cores)
#  - HP_STALL: amortized per-access stall for 2MB fault latency /
#    compaction / bloat-induced pressure, growing with core count.
# Calibrated against Fig. 12-14 (hugepage ~= +10% at 1 core, ~0.9x radix
# at 8 cores).
FRAC_4K = {1: 0.16, 2: 0.27, 4: 0.49, 8: 0.93}
HP_STALL_BASE = 55.0
HP_STALL_PER_CORE = 7.0
QUEUE_K = MM.QUEUE_K        # bounded-linear queue slope (cycles at rho=1)
# ECH: elastic cuckoo tables upsize/rehash under multi-core allocation
# pressure (cuckoo-path inserts + table moves) — per-walk cost grows with
# the number of allocating cores (Skarlatos et al. §upsizing).
ECH_REHASH_QUAD = 5.0    # cost ~ (cores-2)^2: churn once headroom is gone

_INT_MIN = jnp.iinfo(jnp.int32).min

#: relative tolerance for the float-valued results (``cycles``,
#: ``trans_cycles``, ``walk_cycles``) of one simulation run by two XLA
#: backends or reduction orders; the integer-valued counters must match
#: exactly.  Each chunk sums its per-entry f32 cycle terms in an order
#: the backend picks, and a sum of n non-negative f32 terms may be off
#: by up to (n-1)*2^-24 relative: 6.1e-5 at the paper-figure preset's
#: chunk of 1024.  The queue delay of the next chunk reads the perturbed
#: clock back, at most doubling that (1.2e-4); rounded up to 2e-4.
CYCLES_RTOL = 2e-4


@dataclasses.dataclass
class SimResult:
    mechs: Tuple[str, ...]
    cycles: np.ndarray            # (M, C)
    instructions: np.ndarray      # (C,)
    trans_cycles: np.ndarray      # (M, C) translation stall cycles
    walk_cycles: np.ndarray       # (M, C)
    walks: np.ndarray             # (M, C)
    l1tlb_misses: np.ndarray      # (M, C)
    accesses: int
    pte_accesses: np.ndarray      # (M, C)
    pte_l1_hits: np.ndarray       # (M, C)
    pte_mem: np.ndarray           # (M, C)
    data_l1_misses: np.ndarray    # (M, C)
    data_mem: np.ndarray          # (M, C)

    # -- derived metrics ----------------------------------------------------
    def ipc(self) -> np.ndarray:
        return self.instructions[None, :] / self.cycles

    def speedup_vs(self, base: str = "radix") -> Dict[str, float]:
        b = self.mechs.index(base)
        mean_c = self.cycles.mean(axis=1)
        return {m: float(mean_c[b] / mean_c[i])
                for i, m in enumerate(self.mechs)}

    def avg_ptw_latency(self) -> np.ndarray:
        return (self.walk_cycles / np.maximum(self.walks, 1)).mean(axis=1)

    def translation_fraction(self) -> np.ndarray:
        return (self.trans_cycles / self.cycles).mean(axis=1)

    def tlb_miss_rate(self) -> np.ndarray:
        return (self.l1tlb_misses / self.accesses).mean(axis=1)

    def pte_l1_miss_rate(self) -> np.ndarray:
        return 1.0 - (self.pte_l1_hits
                      / np.maximum(self.pte_accesses, 1)).mean(axis=1)

    def data_l1_miss_rate(self) -> np.ndarray:
        return (self.data_l1_misses / self.accesses).mean(axis=1)

    # -- slicing helpers ----------------------------------------------------
    def select(self, mechs: Sequence[str] | str | None = None,
               cores: Sequence[int] | slice | int | None = None
               ) -> "SimResult":
        """Sub-view of the result restricted to ``mechs`` (names, order
        preserved as given) and/or ``cores`` (index/slice/sequence) — the
        figure code uses this instead of raw positional numpy indexing."""
        if isinstance(mechs, str):
            mechs = (mechs,)
        names = self.mechs if mechs is None else tuple(mechs)
        mi = np.asarray([self.mechs.index(n) for n in names])
        if cores is None:
            ci = np.arange(self.cycles.shape[1])
        elif isinstance(cores, slice):
            ci = np.arange(self.cycles.shape[1])[cores]
        else:
            ci = np.atleast_1d(np.asarray(cores))
        mc = lambda a: a[np.ix_(mi, ci)]                     # noqa: E731
        return SimResult(
            mechs=names,
            cycles=mc(self.cycles),
            instructions=self.instructions[ci],
            trans_cycles=mc(self.trans_cycles),
            walk_cycles=mc(self.walk_cycles),
            walks=mc(self.walks),
            l1tlb_misses=mc(self.l1tlb_misses),
            accesses=self.accesses,
            pte_accesses=mc(self.pte_accesses),
            pte_l1_hits=mc(self.pte_l1_hits),
            pte_mem=mc(self.pte_mem),
            data_l1_misses=mc(self.data_l1_misses),
            data_mem=mc(self.data_mem),
        )

    def scalar(self, metric: str, mech: str) -> float:
        """One derived metric for one mechanism, as a plain float:
        ``res.scalar("avg_ptw_latency", "radix")``."""
        return getattr(self.select(mechs=(mech,)), metric)().item()


# ---------------------------------------------------------------------------
# state construction
# ---------------------------------------------------------------------------
def _table_shapes(mach: MachineConfig) -> Dict[str, Tuple[int, int]]:
    """name -> (num_sets, ways) for every LRU table of one (mech, core)."""
    shapes = {
        "l1": (mach.l1d.num_sets, mach.l1d.ways),
        "l1tlb": (mach.l1_dtlb.entries // mach.l1_dtlb.ways,
                  mach.l1_dtlb.ways),
        "l2tlb": (mach.l2_tlb.entries // 12, 12),
        # per-level PWCs as one table: set index IS the walk level
        "pwc": (MAX_PTE, mach.pwc_entries),
    }
    if mach.l2 is not None:
        shapes["l2"] = (mach.l2.num_sets, mach.l2.ways)
    if mach.l3 is not None:
        shapes["l3"] = (mach.l3.num_sets, mach.l3.ways)
    if mach.ctlb_kb > 0:
        # Victima cache-as-TLB: ctlb_kb KB of repurposed cache capacity,
        # one translation per 64B line -> entries = capacity / line.
        # Structurally ABSENT at ctlb_kb=0, so default machines keep
        # their exact compiled graphs (and bit-exact results).
        entries = mach.ctlb_kb * 1024 // 64
        shapes["ctlb"] = (max(entries // mach.ctlb_ways, 1),
                          mach.ctlb_ways)
    return shapes


# ---------------------------------------------------------------------------
# the shape/data split: what compiles vs what rides along as operands
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MachineShape:
    """Everything about a ``MachineConfig`` that determines ARRAY SHAPES
    in the compiled runner: the core count, the (sets, ways) geometry
    of every LRU table, and the memory model's SHAPE half (kind + bank
    geometry — a banked machine carries per-bank row state and five
    extra hit bits).  Two configs with equal shape (and the same
    mechanism walk functions) share one compiled runner — their
    remaining differences (latencies, memory service/row timings,
    huge-page stalls, per-mechanism flags) are plain jit operands.
    Hashable on purpose: this IS the runner-cache key."""

    num_cores: int
    tables: Tuple[Tuple[str, int, int], ...]    # (name, sets, ways)
    #: MemoryModel.shape_key(): ("bounded_linear",) for every bounded
    #: machine — the banked geometry fields are inert there — or
    #: ("banked", num_banks, row_buffer_bytes)
    memory: Tuple = ("bounded_linear",)

    @property
    def hier(self) -> Tuple[str, ...]:
        names = {n for n, _, _ in self.tables}
        return ("l1", "l2", "l3") if "l2" in names else ("l1",)


def machine_shape(mach: MachineConfig) -> MachineShape:
    return MachineShape(
        num_cores=mach.num_cores,
        tables=tuple((n, s, w)
                     for n, (s, w) in _table_shapes(mach).items()),
        memory=mach.memory.shape_key())


def _shape_tables(shape: MachineShape) -> Dict[str, Tuple[int, int]]:
    return {n: (s, w) for n, s, w in shape.tables}


def _data_params(mach: MachineConfig) -> Dict[str, np.float32]:
    """The value-like half of a ``MachineConfig``: every latency the
    timing epilogue consumes, as numpy scalars (NOT Python floats —
    weak-typed constants would bake into the compiled graph and defeat
    the shape/data split).  Memory timing comes from the MemoryModel:
    ``mem_lat`` is the closed-row/full access latency, ``row_save`` the
    precharge+activate cycles an open-row hit skips (0.0 for
    bounded_linear — the key exists either way so dp pytrees always
    match), ``service`` the aggregate (bounded) or per-bank (banked)
    queue service time."""
    return {k: np.float32(v) for k, v in {
        "mem_lat": mach.memory.miss_latency(),
        "row_save": mach.memory.row_hit_save(),
        "l1_lat": mach.l1d.latency,
        "l2_lat": mach.l2.latency if mach.l2 else 0.0,
        "l3_lat": mach.l3.latency if mach.l3 else 0.0,
        "l2tlb_lat": mach.l2_tlb.latency,
        "pwc_lat": mach.pwc_latency,
        "service": mach.memory.service,
        "promo": (HP_STALL_BASE
                  + HP_STALL_PER_CORE * max(mach.num_cores - 1, 0)),
        "ech_rehash": ECH_REHASH_QUAD * max(mach.num_cores - 2, 0) ** 2,
        "ctlb_lat": mach.ctlb_latency,
        # multi-stack NDP memory: the expected extra hop cost of a
        # memory access, (remote fraction) x (hop cycles).  Exactly 0.0
        # at num_stacks=1, keeping single-stack machines bit-exact.
        "stack_pen": ((1.0 - 1.0 / mach.num_stacks)
                      * mach.stack_hop_cycles),
    }.items()}


def _mech_arrays(names: Tuple[str, ...]) -> Dict[str, np.ndarray]:
    """The spec registry lowered to per-mechanism VALUE arrays — jit
    operands as well, so lanes of one dispatch may disagree on walk
    depth, bypass, PWC placement, or huge-page semantics.  Only the
    walk-line FUNCTIONS (:func:`_walk_fns`) stay static."""
    t = tables_for(names)
    return {"n_pte": t.n_pte, "parallel": t.parallel, "bypass": t.bypass,
            "pwc_on": t.pwc_on, "huge": t.huge, "ideal": t.ideal,
            "cache_tlb": t.cache_tlb, "segment": t.segment,
            "colocate": t.colocate}


def _walk_fns(names: Tuple[str, ...]) -> Tuple:
    """The static (code, not data) half of a mechanism tuple: the
    VPN -> PTE-line functions, part of the runner-cache key."""
    return tuple(s.walk_fn for s in specs_for(names))


#: misses accumulated before explicit clear_runner_cache() calls, so
#: runner_cache_info().misses stays MONOTONE across watchdog recoveries
#: (lru_cache.cache_clear resets its own counters)
_CLEARED_MISSES = 0


def runner_cache_info():
    """Cache stats of the compiled-runner cache: ``misses`` counts the
    runners built this process — one per distinct (machine shape,
    walk-fn tuple, chunk) combination, monotone across
    :func:`clear_runner_cache`.  The sweep engine and its tests use
    this to assert "one compile per shape bucket"."""
    info = _chunk_runner.cache_info()
    return info._replace(misses=info.misses + _CLEARED_MISSES)


def clear_runner_cache() -> None:
    """Drop every cached compiled runner.  The watchdog's recovery
    hook: after a hung/timed-out dispatch the wedged executable is the
    prime suspect, so the retry rebuilds it from scratch (the
    persistent .jax_cache still serves unaffected compilations).
    Compile accounting survives: past misses fold into the monotone
    counter :func:`runner_cache_info` reports."""
    global _CLEARED_MISSES
    _CLEARED_MISSES += _chunk_runner.cache_info().misses
    _chunk_runner.cache_clear()


def init_state(mach: MachineConfig, m: int, lanes: int):
    c = mach.num_cores
    # ``lanes`` independent sims, tables (lanes, C, M, sets, ways): every
    # vmap level maps axis 0 with axis-0 outputs, so no per-step
    # transpose (= full table copy) is ever materialized.  Public results
    # stay (M, C) per sim.
    lead = (lanes,)

    def table(sets, ways):
        return {"tags": jnp.zeros(lead + (c, m, sets, ways), jnp.int32),
                "lru": jnp.zeros(lead + (c, m, sets, ways), jnp.int32)}

    st = {name: table(*shape) for name, shape in _table_shapes(mach).items()}
    st["stamp"] = jnp.zeros(lead + (c, m), jnp.int32)
    st["clock"] = jnp.zeros(lead + (m, c), jnp.float32)
    if mach.memory.kind == "banked":
        # per-bank open-row ids (rides the scan carry like the LRU
        # tables; -1 = all rows closed) and per-bank access totals for
        # the per-bank queue windows
        st["bank_row"] = jnp.full(lead + (c, m, mach.memory.num_banks),
                                  -1, jnp.int32)
        st["mem_accs"] = jnp.zeros(lead + (m, mach.memory.num_banks),
                                   jnp.float32)
    else:
        st["mem_accs"] = jnp.zeros(lead + (m,), jnp.float32)
    st["counters"] = {k: jnp.zeros(lead + (m, c), jnp.float32)
                      for k in ("trans", "walks", "walk_cyc", "l1tlb_miss",
                                "pte_acc", "pte_l1_hit", "pte_mem",
                                "data_l1_miss", "data_mem")}
    return st


# ---------------------------------------------------------------------------
# the model: sequential hit extraction + vectorized timing
# ---------------------------------------------------------------------------
#: the ``jax.named_scope`` of each part of a scan step's lookups, carried
#: into the ``op_name`` metadata of the compiled runner's instructions:
#: the TLB lookups (L1/L2 DTLB, cache-as-TLB), the page walk (PWC and
#: the PTE lines' cache and bank-row lookups), the data line's lookups
SCOPES = ("tlb", "walk", "data")


def _line_scope(site: int) -> str:
    """Scope of one of a step's five line sites: pte0..pte3, then data."""
    return "walk" if site < MAX_PTE else "data"


def lru_access(tab, num_sets: int, key, en, stamp, *, set_override=None):
    """One scalar lookup+fill of a set-associative LRU table, the only
    one the model has (caches, TLBs, PWCs, cache-as-TLB).

    ``tab`` is ``{"tags": (sets, ways), "lru": (sets, ways)}`` int32 as
    :func:`init_state` builds it (stored tag = ``key // sets + 1``, 0 =
    invalid; ``lru`` = the stamp of the way's last use).  An enabled
    access (``en``) hits where the set holds the tag and always fills:
    the matching way on a hit, else the least recently stamped way, is
    written with the tag and ``stamp``.  A disabled access reports a
    miss and leaves the table as it was.  ``set_override`` pins the set
    index (the PWC's set is the walk level) and stores ``key + 1``.
    Returns ``(table, hit)``.  Scalar set index keeps XLA on the
    dynamic-slice fast path — this is the per-step hot loop."""
    if set_override is None:
        s = jax.lax.rem(key, num_sets)
        tag = jax.lax.div(key, num_sets) + 1
    else:
        s = set_override                        # pwc: set = walk level
        tag = key + 1
    row_tags = tab["tags"][s]
    row_lru = tab["lru"][s]
    match = row_tags == tag
    hit = match.any() & en
    # a match wins the argmin outright; otherwise it picks true LRU
    way = jnp.argmin(jnp.where(match, _INT_MIN, row_lru))
    s_safe = jnp.where(en, s, num_sets)         # disabled -> dropped
    new = {"tags": tab["tags"].at[s_safe, way].set(tag, mode="drop"),
           "lru": tab["lru"].at[s_safe, way].set(stamp, mode="drop")}
    return new, hit


def _build_model(shape: MachineShape):
    """The model, parameterized ONLY by shape: every latency and every
    per-mechanism flag arrives at trace time as an operand (``dp`` data
    params / ``mt`` mechanism tables), so one build serves a whole
    sensitivity grid.  Both carry a leading lane axis — lanes of one
    dispatch can simulate different machines and mechanism variants."""
    hier = shape.hier
    shapes = _shape_tables(shape)
    has_ctlb = "ctlb" in shapes
    has_banked = shape.memory[0] == "banked"
    if has_banked:
        n_banks = int(shape.memory[1])
        lines_per_row = int(shape.memory[2]) // MM.LINE_BYTES

    # hit-bit layout of the packed per-entry int32
    #   0: l1tlb  1: l2tlb  2..5: pwc level  6+5*h..10+5*h: hierarchy
    #   level h hits for [pte0..pte3, data]; when the machine HAS a
    #   cache-as-TLB its hit bit is APPENDED after everything else so
    #   pre-existing bit indices (and values) never move; a banked
    #   memory likewise APPENDS five row-buffer-hit bits (one per line
    #   site) after that.  Worst case 6 + 15 + 1 + 5 = 27 <= 31.
    ctlb_bit = 6 + 5 * len(hier)
    bank_bit = ctlb_bit + (1 if has_ctlb else 0)
    n_bits = bank_bit + (5 if has_banked else 0)
    assert n_bits <= 31

    # LRU stamp slots: every access site gets a fixed offset so one scalar
    # stamp per (mech, core) serves all tables with program-order ties;
    # the ctlb slot is likewise appended at the end
    n_slots = 2 + MAX_PTE + 5 * len(hier) + (1 if has_ctlb else 0)
    ctlb_slot = 2 + MAX_PTE + 5 * len(hier)

    def per_mc(sub, stamp, vpn, off, pte_lines, is4k, valid, mt):
        """Hit extraction for one (mech, core): touches every table once
        per gated access site, returns the packed hit bits.  ``mt`` is
        this mechanism's scalar flag/depth values (vmapped off the lane
        and M axes of the mechanism tables)."""
        ideal = mt["ideal"]
        huge = mt["huge"]
        byp = mt["bypass"]

        with jax.named_scope("tlb"):
            tlb_key = jnp.where(huge & ~is4k,
                                (vpn >> HUGE_SHIFT) | (1 << 26), vpn)
            # direct-segment mechanisms translate in-segment accesses
            # (the non-fragmented share, ~is4k) via base/limit
            # registers: no TLB lookup, no walk — only the
            # fragmentation-broken rest enters the translation
            # machinery below
            en0 = valid & ~ideal & ~(mt["segment"] & ~is4k)
            sub["l1tlb"], h_l1tlb = lru_access(sub["l1tlb"],
                                               shapes["l1tlb"][0],
                                               tlb_key, en0, stamp)
            en1 = en0 & ~h_l1tlb
            sub["l2tlb"], h_l2tlb = lru_access(sub["l2tlb"],
                                               shapes["l2tlb"][0],
                                               tlb_key, en1, stamp + 1)
            walk = en1 & ~h_l2tlb
            if has_ctlb:
                # cache-as-TLB probe after an L2-TLB miss: a hit short-
                # circuits the walk for cache_tlb mechanisms
                en_ct = walk & mt["cache_tlb"]
                sub["ctlb"], h_ctlb = lru_access(sub["ctlb"],
                                                 shapes["ctlb"][0],
                                                 tlb_key, en_ct,
                                                 stamp + ctlb_slot)
                walk = walk & ~h_ctlb

        with jax.named_scope("walk"):
            # hugepage 4KB-fallback regions walk like radix (4 levels)
            eff_n = jnp.where(huge & is4k, MAX_PTE, mt["n_pte"])
            bits = [h_l1tlb, h_l2tlb]
            pwc_hits = []
            for lvl in range(MAX_PTE):
                en = walk & (lvl < eff_n) & mt["pwc_on"][lvl]
                sub["pwc"], h = lru_access(sub["pwc"], shapes["pwc"][0],
                                           pte_lines[lvl], en,
                                           stamp + 2 + lvl,
                                           set_override=lvl)
                pwc_hits.append(h)
                bits.append(h)

        data_line = vpn * 64 + off
        lines = [pte_lines[lvl] for lvl in range(MAX_PTE)] + [data_line]
        # enables at the top of the hierarchy; lower levels chain on miss
        ens = [walk & (lvl < eff_n) & ~pwc_hits[lvl] & ~byp
               for lvl in range(MAX_PTE)] + [valid]
        for h_i, name in enumerate(hier):
            slot = stamp + 2 + MAX_PTE + 5 * h_i
            nxt = []
            for i in range(5):
                with jax.named_scope(_line_scope(i)):
                    sub[name], h = lru_access(sub[name], shapes[name][0],
                                              lines[i], ens[i], slot + i)
                nxt.append(ens[i] & ~h)
                bits.append(h)
            ens = nxt

        if has_ctlb:
            bits.append(h_ctlb)          # appended: old bit indices keep
        if has_banked:
            # DRAM row-buffer tracking: one open-row id per bank rides
            # the carry like the LRU tables.  Only accesses that
            # actually reach memory touch a bank — bypassed PTE lines
            # go straight there, everything else is the post-hierarchy
            # miss chain (``ens`` after the loop above).  Sites update
            # in program order (pte0..pte3, then data).
            mem_ens = [(walk & (lvl < eff_n) & ~pwc_hits[lvl] & byp)
                       | ens[lvl] for lvl in range(MAX_PTE)]
            mem_ens.append(ens[MAX_PTE])
            rows = sub["bank_row"]
            for i in range(5):
                with jax.named_scope(_line_scope(i)):
                    bk = jax.lax.rem(jax.lax.div(lines[i], lines_per_row),
                                     n_banks)
                    rw = jax.lax.div(lines[i], lines_per_row * n_banks)
                    bits.append((rows[bk] == rw) & mem_ens[i])
                    rows = rows.at[jnp.where(mem_ens[i], bk, n_banks)].set(
                        rw, mode="drop")
            sub["bank_row"] = rows
        packed = (jnp.stack(bits)
                  * (1 << jnp.arange(n_bits, dtype=jnp.int32))).sum()
        return sub, stamp + n_slots, packed

    # inner vmap over mechanisms, outer over lanes — every mapped input
    # and output uses axis 0 so XLA never transposes the carried tables.
    # A lane is one core of one simulation: the B (independent-
    # simulation) axis is FUSED into the core axis, the layout XLA-CPU
    # handles well, whereas a literal third vmap level regresses the
    # per-step gathers.  ``valid`` and the mechanism tables are per-lane
    # inputs: per-sim trace lengths and per-sim mechanism values.
    per_core = jax.vmap(per_mc,
                        in_axes=(0, 0, None, None, 0, None, None, 0))
    all_lanes = jax.vmap(per_core,
                         in_axes=(0, 0, 0, 0, 0, 0, 0, 0))

    def make_step(mt):
        def step(carry, x):
            sub, stamp = carry
            vpn, off, pte_lines, is4k, valid = x
            sub, stamp, packed = all_lanes(sub, stamp, vpn, off,
                                           pte_lines, is4k, valid, mt)
            return (sub, stamp), packed
        return step

    def epilogue(packed, work, is4k, valid, q, mt, dp, lines=None):
        """Vectorized timing over the whole chunk.

        packed: (T, M, C) hit bits, where C is the fused lanes axis
        (B*cores); work/is4k/valid: (T, C); q: (M, C) queue delay (per-sim
        windows expanded per lane), constant within the chunk.  Banked
        memory generalizes q to a trailing bank axis (M, C, banks) and
        passes ``lines`` (T, M, C, 5), the line ids of the five access
        sites, so each access gathers ITS bank's queue window and
        row-hit discount.
        ``mt`` mechanism tables ((C, M) leaves) and ``dp`` data params
        ((C,) leaves) are operands.
        Re-derives the same gates the scan used (pure functions of the
        hit bits) and produces the (M, C) counter/clock deltas.
        """
        def bit(i):
            return ((packed >> i) & 1).astype(bool)

        def mb(a):          # mech table -> broadcast over (T, M, C)
            return a.T[None]

        def d3(v):          # data param -> broadcast over (T, M, C)
            return v[None, None, :]

        def d4(v):          # data param -> broadcast over (T, M, C, 5)
            return v[None, None, :, None]

        validb = valid[:, None, :]                          # (T, 1, C)
        is4kb = is4k[:, None, :]                            # (T, 1, C)
        idealb = mb(mt["ideal"])
        hugeb = mb(mt["huge"])
        bypb = mb(mt["bypass"])
        mem4 = d4(dp["mem_lat"])
        hier_lat = [dp["l1_lat"], dp["l2_lat"], dp["l3_lat"]][:len(hier)]
        # multi-stack remote-hop penalty per memory access: co-locating
        # mechanisms place frames in the local stack and dodge ~90% of
        # it.  stack_pen is 0.0 on single-stack machines, so this is an
        # exact +0.0 there (bit-stable vs the pre-zoo engine).
        pen = d3(dp["stack_pen"]) * jnp.where(mb(mt["colocate"]),
                                              0.1, 1.0)
        pen4 = pen[..., None]

        # per-access memory cost at each of the five line sites.
        # Bounded: flat latency + the mech's aggregate queue window.
        # Banked: closed-row latency, minus the precharge+activate the
        # scan-tracked row hit skips, plus the access's OWN bank's queue
        # window (gathered by bank index) — contiguous flat-leaf spans
        # keep their row open, scattered radix nodes mostly do not.
        if has_banked:
            rowhit = jnp.stack([bit(bank_bit + i) for i in range(5)], -1)
            bank5 = (lines // lines_per_row) % n_banks     # (T, M, C, 5)
            q_acc = jnp.take_along_axis(
                jnp.broadcast_to(q[None], packed.shape + (n_banks,)),
                bank5, axis=-1)                            # (T, M, C, 5)
            mem_cost = (mem4 - rowhit * d4(dp["row_save"])
                        + q_acc + pen4)
        else:
            qb = q[None]                                    # (1, M, C)
            mem_cost = mem4 + qb[..., None] + pen4

        h_l1tlb, h_l2tlb = bit(0), bit(1)
        en0 = validb & ~idealb & ~(mb(mt["segment"]) & ~is4kb)
        walk = en0 & ~h_l1tlb & ~h_l2tlb                    # (T, M, C)
        if has_ctlb:
            ctlb_probe = walk & mb(mt["cache_tlb"])
            walk = walk & ~bit(ctlb_bit)
        eff_n = jnp.where(hugeb & is4kb, MAX_PTE, mb(mt["n_pte"]))

        # hierarchy latency per line (pte0..3, data): chain the per-level
        # hit bits top-down; a line that misses everywhere pays memory + q
        lat = jnp.zeros(packed.shape + (5,), jnp.float32)
        reached = jnp.ones(packed.shape + (5,), bool)
        went_mem = jnp.ones(packed.shape + (5,), bool)
        for h_i in range(len(hier)):
            h = jnp.stack([bit(6 + 5 * h_i + i) for i in range(5)], -1)
            lat = lat + jnp.where(reached, d4(hier_lat[h_i]), 0.0)
            went_mem = went_mem & ~h
            reached = reached & ~h
        lat = lat + jnp.where(reached, mem_cost, 0.0)

        # per-PTE-level walk latency: PWC hit beats everything; NDPage
        # bypass goes straight to memory; cached mechanisms pay the chain
        pwc_hit = jnp.stack([bit(2 + lvl) for lvl in range(MAX_PTE)], -1)
        pte_en = (walk[..., None]
                  & (jnp.arange(MAX_PTE) < eff_n[..., None]))
        need_mem = pte_en & ~pwc_hit
        pte_lat = jnp.where(bypb[..., None],
                            mem_cost[..., :MAX_PTE],
                            lat[..., :MAX_PTE])
        pte_lat = jnp.where(pwc_hit, d4(dp["pwc_lat"]), pte_lat)
        pte_lat = jnp.where(pte_en, pte_lat, 0.0)

        # parallel (ECH) walks: all probes issue simultaneously and the
        # walk completes when the HITTING probe returns — one access
        # latency plus own-bank conflict + issue overhead.  The extra
        # probes only add traffic (counted in pte_mem -> queue pressure).
        # Multi-core: amortized cuckoo upsizing/rehash contention.
        walk_cyc = jnp.where(mb(mt["parallel"]),
                             pte_lat.max(-1) + 2.0 + d3(dp["ech_rehash"]),
                             pte_lat.sum(-1))

        trans = jnp.where(walk, walk_cyc, 0.0)
        if has_ctlb:
            # the cache-as-TLB probe is serial after the L2-TLB miss:
            # paid on hit AND miss; a hit replaces the walk entirely
            trans = trans + jnp.where(ctlb_probe, d3(dp["ctlb_lat"]),
                                      0.0)
        trans = jnp.where(en0 & ~h_l1tlb, d3(dp["l2tlb_lat"]) + trans, 0.0)
        trans = trans + jnp.where(hugeb & validb, d3(dp["promo"]), 0.0)

        pte_l1_hit = jnp.stack([bit(6 + i) for i in range(MAX_PTE)], -1)
        pte_mem = jnp.where(need_mem,
                            jnp.where(bypb[..., None], True,
                                      went_mem[..., :MAX_PTE]), False)
        data_mem = validb & went_mem[..., MAX_PTE]
        dlat = jnp.where(validb, lat[..., MAX_PTE], 0.0)

        step_cyc = jnp.where(
            validb,
            work[:, None, :] + 1.0 + trans + (dlat - d3(dp["l1_lat"])),
            0.0)

        # NB: XLA-CPU's axis-0 reduce keeps one association for every
        # lane width except 1 (rank-collapse special case), so these f32
        # sums are bit-stable across batch sizes as long as the lane
        # minor-dim stays >= 2 — which _prepare guarantees by padding
        # 1-lane runs (integer-valued counters are order-exact
        # regardless).  tests/test_batch.py pins this.
        f32 = lambda a: a.astype(jnp.float32).sum(axis=0)   # noqa: E731
        cnt = {
            "trans": trans.sum(axis=0),
            "walks": f32(walk),
            "walk_cyc": jnp.where(walk, walk_cyc, 0.0).sum(axis=0),
            "l1tlb_miss": f32(en0 & ~h_l1tlb),
            "pte_acc": need_mem.astype(jnp.float32).sum(axis=(0, -1)),
            "pte_l1_hit": pte_l1_hit.astype(jnp.float32).sum(axis=(0, -1)),
            "pte_mem": pte_mem.astype(jnp.float32).sum(axis=(0, -1)),
            "data_l1_miss": f32(validb & ~bit(6 + MAX_PTE)),
            "data_mem": f32(data_mem),
        }
        if has_banked:
            # per-bank demand totals for the per-bank queue windows:
            # (M, C, banks) — the caller folds the core axis per sim
            acc5 = jnp.concatenate([pte_mem, data_mem[..., None]], -1)
            onehot = bank5[..., None] == jnp.arange(n_banks)
            mem_n = (acc5[..., None] & onehot).astype(
                jnp.float32).sum(axis=(0, 3))
        else:
            mem_n = (pte_mem.astype(jnp.float32).sum(axis=(0, -1))
                     + data_mem.astype(jnp.float32).sum(axis=0))
        return cnt, step_cyc.sum(axis=0), mem_n

    return make_step, epilogue


# ---------------------------------------------------------------------------
# chunked driver
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _chunk_runner(shape: MachineShape, walk_fns: Tuple, chunk: int):
    """One jitted (scan + epilogue) over a chunk of B simulations,
    specialized per (machine SHAPE, walk-fn tuple, chunk length) and
    cached for the life of the process.  xs arrive as (T, B, C) (valid:
    (T, B)), state carries a leading B, and the queue window is tracked
    per sim.  Machine latencies (``dp``) and per-mechanism flag tables
    (``mt``) are operands with a leading B (heterogeneous lanes), so
    every value-only machine or mechanism variant reuses the same
    compiled runner.  State buffers are donated: chunk i+1 reuses chunk
    i's memory.  The per-mechanism PTE walk lines are derived from the
    VPNs inside the jit so the host never materializes (T, C, M,
    MAX_PTE).  One jitted callable serves every B (jit re-traces per
    shape) and every sharding of the B axis."""
    make_step, epilogue = _build_model(shape)
    table_names = tuple(n for n, _, _ in shape.tables)
    has_banked = shape.memory[0] == "banked"
    # banked memory: per-bank open-row ids join the scan carry, and the
    # epilogue needs the raw line ids to gather per-bank queue windows
    carry_names = table_names + (("bank_row",) if has_banked else ())

    def walk_lines(vpn, is4k, huge):
        """(T, C) vpns -> (T, C, M, MAX_PTE) PTE line ids.  ``huge`` is
        runtime data (C, M): huge-page mechanisms blend in the radix
        fallback lines for fragmented (4KB) regions."""
        radix = _pad_lines(PT.radix4_walk_lines(vpn))
        per_mech = []
        for i, fn in enumerate(walk_fns):
            if fn is None:
                lines = jnp.zeros_like(radix)
            elif fn is PT.radix4_walk_lines:
                lines = radix
            else:
                lines = _pad_lines(fn(vpn))
            lines = jnp.where(huge[None, :, i, None] & is4k[..., None],
                              radix, lines)
            per_mech.append(lines)
        return jnp.stack(per_mech, axis=-2)

    def _queue(clock, mem_accs, service):
        # queue delay from demand measured so far (per mech, per sim).
        # Bounded-linear law: DRAM degrades gently up to saturation (an
        # M/M/1 knee over-penalizes small traffic deltas at high load).
        # Held constant within the chunk.  Banked: the same law applied
        # per BANK (mem_accs carries a trailing bank axis and service
        # is the per-bank occupancy) — traffic on one bank never delays
        # another.
        elapsed = jnp.maximum(clock.mean(axis=-1), 1.0)
        if has_banked:
            rate = mem_accs / elapsed[..., None]
            return MM.queue_delay(rate, service[:, None, None])  # (B,M,bk)
        rate = mem_accs / elapsed                 # aggregate accesses/cycle
        svc = service[:, None]
        rho = jnp.clip(rate * svc, 0.0, 0.96)
        return svc * rho * QUEUE_K                # (B, M)

    def _lines5(pte, vpn, off):
        # the five access sites' line ids in epilogue orientation
        # (T, M, C, 5): pte0..3 from the walk, then the data line
        pm = jnp.swapaxes(pte, 1, 2)
        dl = (vpn * 64 + off)[:, None, :, None]
        return jnp.concatenate(
            [pm, jnp.broadcast_to(dl, pm.shape[:-1] + (1,))], -1)

    def run_batch(state, xs, mt, dp):
        """B sims as one dispatch.  State arrives (B, C, M, ...) and is
        reshaped — free, the leading axes are contiguous — onto the
        fused (B*C, M, ...) lane layout the two vmap levels run; valid
        bits, queue windows, mechanism tables, and data params are
        expanded per lane.  Public counters stay per-sim (B, M, C).
        Its name is the jit's, ``jit(run_batch)``, which opens the
        op_name of every instruction a device trace shows."""
        vpn, off, work, is4k, valid = xs          # (T, B, C); valid (T, B)
        t, b, c = vpn.shape
        m = state["stamp"].shape[-1]
        fuse = lambda a: a.reshape((t, b * c) + a.shape[3:])   # noqa: E731
        vpn, off, work, is4k = (fuse(a) for a in (vpn, off, work, is4k))
        valid = jnp.repeat(valid, c, axis=1)      # (T, B*C)
        mt_l = {k: jnp.repeat(v, c, axis=0) for k, v in mt.items()}
        dp_l = {k: jnp.repeat(v, c, axis=0) for k, v in dp.items()}
        pte = walk_lines(vpn, is4k, mt_l["huge"])
        q = _queue(state["clock"], state["mem_accs"],
                   dp["service"])                 # (B, M) / (B, M, bk)
        if has_banked:                            # -> (M, B*C, bk)
            q_lane = jnp.repeat(jnp.moveaxis(q, 0, 1), c, axis=1)
        else:
            q_lane = jnp.repeat(q.T, c, axis=1)   # (M, B*C)

        carry = (jax.tree.map(lambda a: a.reshape((b * c,) + a.shape[2:]),
                              {k: state[k] for k in carry_names}),
                 state["stamp"].reshape(b * c, m))
        (tabs, stamp), packed = jax.lax.scan(
            make_step(mt_l), carry, (vpn, off, pte, is4k, valid))
        cnt, cyc, mem_n = epilogue(
            jnp.swapaxes(packed, 1, 2), work, is4k, valid, q_lane,
            mt_l, dp_l,
            lines=_lines5(pte, vpn, off) if has_banked else None)

        def unfuse_mc(a):                 # (M, B*C, ...) -> (B, M, C, ...)
            return jnp.moveaxis(
                a.reshape((a.shape[0], b, c) + a.shape[2:]), 1, 0)

        new_state = jax.tree.map(
            lambda a: a.reshape((b, c) + a.shape[1:]), tabs)
        new_state["stamp"] = stamp.reshape(b, c, m)
        new_state["clock"] = state["clock"] + unfuse_mc(cyc)
        new_state["mem_accs"] = (state["mem_accs"]
                                 + unfuse_mc(mem_n).sum(axis=2))
        new_state["counters"] = {
            k: state["counters"][k] + unfuse_mc(cnt[k])
            for k in state["counters"]}
        return new_state

    return jax.jit(run_batch, donate_argnums=(0,))


# a spec re-registered with overwrite=True must not keep serving runners
# compiled from the old MechTables/walk_fn
_mechanisms.on_register(_chunk_runner.cache_clear)


def _resolve_trace(trace, num_cores: int, length: int | None):
    """Accept a ``"trace:<path>"`` workload spec anywhere a trace dict
    is expected: resolved through :func:`repro.workloads.generate_trace`
    (which dispatches to the real-trace ingest layer), so every engine
    entry point replays real traces with zero engine changes."""
    if isinstance(trace, str):
        from repro.workloads import generate_trace, parse_workload_spec
        parse_workload_spec(trace)       # fail loudly at the boundary
        return generate_trace(trace, num_cores, length=length)
    return trace


def simulate(mach: MachineConfig, trace: Dict[str, np.ndarray] | str,
             length: int | None = None, *,
             mechs: Tuple[str, ...] | None = None,
             chunk: int = DEFAULT_CHUNK) -> SimResult:
    """Run the registered mechanisms over a multi-core trace on ``mach``.

    ``mechs`` selects/orders mechanisms from the spec registry (default:
    the paper's five).  The run is a batch of one on one device
    (:func:`simulate_batch`): the trace is zero-padded to a multiple of
    ``chunk`` (padding is masked out of every counter) and streamed
    through the cached chunk runner.  ``trace`` may be a
    ``"trace:<path>"`` spec for an ingested real trace.
    """
    return simulate_batch(mach, [trace], length, mechs=mechs, chunk=chunk,
                          devices=1)[0]


def simulate_batch(mach: MachineConfig,
                   traces: Sequence[Dict[str, np.ndarray] | str],
                   length: int | None = None, *,
                   mechs: Tuple[str, ...] | None = None,
                   chunk: int = DEFAULT_CHUNK,
                   devices: int | None = None,
                   timings: Dict | None = None) -> List[SimResult]:
    """Run B independent simulations sharing ``mach`` as ONE
    chunked-scan dispatch.

    ``traces`` is a sequence of trace dicts (each ``(num_cores, T_i)``)
    — or ``"trace:<path>"`` specs, resolved through the real-trace
    ingest layer; lanes with shorter traces are masked with per-sim
    valid bits, so mixed-length buckets are fine.  Results are bit-exact vs calling
    :func:`simulate` per trace — state is laid out ``(B, C, M, sets,
    ways)`` and fused to a wider lane axis at dispatch; lanes never
    interact.  Thin wrapper over :func:`simulate_batch_varied` with
    every lane on the same machine and mechanism tuple.

    ``devices`` shards the B axis over that many XLA devices (default:
    all of them when ``SIM_DEVICES`` forced multiple host devices,
    else 1); B is padded to a device multiple with all-invalid lanes.
    ``timings``, if given, is filled with wall clock for the benchmark
    drivers: "compile_s" (ahead-of-time ``lower().compile()`` of the
    chunk runner), "run_s" (every chunk, synced, after compilation),
    "total_s" (their sum), "chunks", "pack_s" and "transfer_s" (see
    :func:`simulate_batch_varied`).
    """
    names = DEFAULT_MECHS if mechs is None else tuple(mechs)
    return simulate_batch_varied(
        [SimJob(mach, tr, names) for tr in traces], length,
        chunk=chunk, devices=devices, timings=timings)


@dataclasses.dataclass
class SimJob:
    """One lane of a varied batch: a machine, its trace, and the
    mechanism tuple to evaluate.  All jobs of one
    :func:`simulate_batch_varied` call must share the machine SHAPE
    (:func:`machine_shape`) and the mechanisms' walk-fn tuple —
    everything value-like (latencies, service time, bypass/PWC/huge
    flags, walk depth) may differ per lane."""

    mach: MachineConfig
    trace: Dict[str, np.ndarray] | str
    mechs: Tuple[str, ...] = DEFAULT_MECHS


def simulate_batch_varied(jobs: Sequence[SimJob],
                          length: int | None = None, *,
                          chunk: int = DEFAULT_CHUNK,
                          devices: int | None = None,
                          timings: Dict | None = None) -> List[SimResult]:
    """B heterogeneous (machine, trace, mechanisms) jobs as ONE
    chunked-scan dispatch — the sweep engine's bucket primitive.

    The jobs must form one *shape bucket*: equal :func:`machine_shape`
    and equal mechanism walk-fn tuples (a ``ValueError`` names the
    offender otherwise).  Everything value-like varies per lane via the
    mt/dp operand stacks, so e.g. a memory-latency grid or an L1-bypass
    ablation is a single dispatch with zero extra compiles.

    With :mod:`repro.util.spans` on, the call is a ``repro.sim.job``
    span whose children are ``pack``, ``transfer``, ``compile`` (with
    ``timings`` only), ``run`` (one ``chunk`` per dispatch, then
    ``sync``) and ``readback``, each carrying the lane count.
    ``timings`` is filled from the same clock reads, in seconds:
    "compile_s", "run_s", "total_s" (their sum), "chunks", and the host's
    preparation, "pack_s" (numpy packing, huge-page hash, valid mask)
    and "transfer_s" (upload, state init, chunk slicing).
    """
    b = len(jobs)
    if b == 0:
        return []
    timed = timings is not None
    with spans.span("repro.sim.job", lanes=b):
        p = _prepare(jobs, length, chunk, devices, timings)
        if timed:
            # ahead-of-time trace + compile (a no-op when this process
            # has compiled the runner for these shapes already); the loop
            # below then dispatches the cached executable
            with spans.span("repro.sim.compile", clock=True,
                            lanes=b) as sp:
                p.runner.lower(p.state, p.chunks[0], p.mt, p.dp).compile()
            timings["compile_s"] = sp.seconds
        state = p.state
        with spans.span("repro.sim.run", clock=timed, lanes=b) as sp:
            for k, xs_k in enumerate(p.chunks):
                with spans.span("repro.sim.chunk", lanes=b, index=k):
                    state = p.runner(state, xs_k, p.mt, p.dp)
            with spans.span("repro.sim.sync", lanes=b):
                state = jax.block_until_ready(state)
        if timed:
            timings["chunks"] = len(p.chunks)
            timings["run_s"] = sp.seconds
            timings["total_s"] = timings["compile_s"] + timings["run_s"]

        with spans.span("repro.sim.readback", lanes=b):
            cnt = {k: np.asarray(v) for k, v in state["counters"].items()}
            clock = np.asarray(state["clock"])
            return [SimResult(
                mechs=p.jobs[i].mechs,
                cycles=clock[i],
                instructions=np.asarray((p.works[i] + 1).sum(axis=1),
                                        np.float64),
                trans_cycles=cnt["trans"][i],
                walk_cycles=cnt["walk_cyc"][i],
                walks=cnt["walks"][i],
                l1tlb_misses=cnt["l1tlb_miss"][i],
                accesses=p.lens[i],
                pte_accesses=cnt["pte_acc"][i],
                pte_l1_hits=cnt["pte_l1_hit"][i],
                pte_mem=cnt["pte_mem"][i],
                data_l1_misses=cnt["data_l1_miss"][i],
                data_mem=cnt["data_mem"][i],
            ) for i in range(b)]


def lower_batch(jobs: Sequence[SimJob], length: int | None = None, *,
                chunk: int = DEFAULT_CHUNK,
                devices: int | None = None) -> "jax.stages.Lowered":
    """The chunk runner lowered for ``jobs`` exactly as
    :func:`simulate_batch_varied` dispatches it (same arguments, shapes
    and shardings).  Its compiled text names the instructions a device
    trace of the job shows, with each one's :data:`SCOPES` part in its
    ``op_name`` metadata."""
    p = _prepare(jobs, length, chunk, devices)
    return p.runner.lower(p.state, p.chunks[0], p.mt, p.dp)


@dataclasses.dataclass
class _Prepared:
    """One bucket's jobs packed, on the device and cut into chunks."""
    jobs: List[SimJob]
    runner: object
    state: Dict
    chunks: List
    mt: Dict
    dp: Dict
    lens: List[int]
    works: List[np.ndarray]


def _prepare(jobs: Sequence[SimJob], length: int | None, chunk: int,
             devices: int | None, timings: Dict | None = None
             ) -> _Prepared:
    """Checks the bucket, packs its traces on the host (span
    ``repro.sim.pack``) and moves them and the initial state to the
    devices (``repro.sim.transfer``), filling ``timings``' "pack_s" and
    "transfer_s" where it is given."""
    b = len(jobs)
    jobs = [j if not isinstance(j.trace, str)
            else dataclasses.replace(
                j, trace=_resolve_trace(j.trace, j.mach.num_cores, length))
            for j in jobs]
    shape = machine_shape(jobs[0].mach)
    wf = _walk_fns(jobs[0].mechs)
    m = len(specs_for(jobs[0].mechs))
    c = shape.num_cores
    for j in jobs:
        if machine_shape(j.mach) != shape:
            raise ValueError(
                f"job {j.mach.name!r} breaks the shape bucket: "
                f"{machine_shape(j.mach)} != {shape} — split the batch "
                "by machine_shape() first")
        if _walk_fns(j.mechs) != wf:
            raise ValueError(
                f"job mechs {j.mechs} have different walk functions "
                "than the bucket's — bucket by walk-fn tuple first")

    timed = timings is not None
    with spans.span("repro.sim.pack", clock=timed, lanes=b) as sp_pack:
        vpns, offs, works, lens = [], [], [], []
        for j in jobs:
            vpn = j.trace["vpn"][:, :length] if length else j.trace["vpn"]
            assert vpn.shape[0] == c, (vpn.shape[0], c)
            vpns.append(vpn)
            offs.append(j.trace["off"][:, : vpn.shape[1]])
            works.append(j.trace["work"][:, : vpn.shape[1]])
            lens.append(vpn.shape[1])
        t_pad = max(lens) + (-max(lens)) % chunk

        ndev = devices
        if ndev is None:
            ndev = (len(jax.devices()) if os.environ.get("SIM_DEVICES")
                    else 1)
        ndev = max(1, min(ndev, len(jax.devices()), b))
        bp = b + (-b) % ndev                 # pad B to a device multiple
        if bp * c < 2:
            bp = 2      # keep the fused lane axis >= 2 wide: XLA's
            #             width-1 reduce reassociates (see epilogue)

        def pack(arrs, dtype):
            out = np.zeros((t_pad, bp, c), dtype)
            for i, a in enumerate(arrs):
                out[: lens[i], i] = np.ascontiguousarray(a.T)
            return out

        # huge-page fragmentation: which 2MB regions fell back to 4KB
        is4ks = []
        for j, v in zip(jobs, vpns):
            frac = FRAC_4K.get(j.mach.num_cores,
                               min(0.93, 0.05 + 0.11 * j.mach.num_cores))
            is4ks.append((_hash_np(v >> HUGE_SHIFT) % 1000)
                         < int(frac * 1000))
        valid = np.zeros((t_pad, bp), bool)
        for i, n in enumerate(lens):
            valid[:n, i] = True
        xs = (pack(vpns, np.int32), pack(offs, np.int32),
              pack(works, np.float32), pack(is4ks, bool), valid)

        # per-lane value stacks; pad lanes reuse job 0 (their valid bits
        # are all False, so their counters are discarded anyway)
        pad_jobs = list(jobs) + [jobs[0]] * (bp - b)
        mts = [_mech_arrays(j.mechs) for j in pad_jobs]
        dps = [_data_params(j.mach) for j in pad_jobs]
        mt = {k: np.stack([t[k] for t in mts]) for k in mts[0]}
        dp = {k: np.stack([d[k] for d in dps]) for k in dps[0]}

    with spans.span("repro.sim.transfer", clock=timed, lanes=b) as sp_xfer:
        xs = tuple(jnp.asarray(a) for a in xs)
        mt = {k: jnp.asarray(v) for k, v in mt.items()}
        dp = {k: jnp.asarray(v) for k, v in dp.items()}
        state = init_state(jobs[0].mach, m, bp)
        if ndev > 1:
            mesh = Mesh(np.asarray(jax.devices()[:ndev]), ("b",))
            st_sh = NamedSharding(mesh, P("b"))   # state: B leading
            xs_sh = NamedSharding(mesh, P(None, "b"))   # xs: (T, B, ...)
            state = jax.tree.map(lambda a: jax.device_put(a, st_sh), state)
            xs = tuple(jax.device_put(a, xs_sh) for a in xs)
            mt = {k: jax.device_put(v, st_sh) for k, v in mt.items()}
            dp = {k: jax.device_put(v, st_sh) for k, v in dp.items()}
        chunks = [jax.tree.map(lambda a: a[i:i + chunk], xs)
                  for i in range(0, t_pad, chunk)]
    if timed:
        timings["pack_s"] = sp_pack.seconds
        timings["transfer_s"] = sp_xfer.seconds
    return _Prepared(jobs, _chunk_runner(shape, wf, chunk),
                     state, chunks, mt, dp, lens, works)


def _pad_lines(a: jnp.ndarray) -> jnp.ndarray:
    """Pad (T, C, d) walk lines to (T, C, MAX_PTE)."""
    pad = [(0, 0)] * (a.ndim - 1) + [(0, MAX_PTE - a.shape[-1])]
    return jnp.pad(a, pad)


def _hash_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32) ^ np.uint32(0x9E3779B9)
    x = (x ^ (x >> 16)) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * np.uint32(0x846CA68B)
    return x ^ (x >> 16)

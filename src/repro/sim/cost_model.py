"""Translation cost model: simulator mechanism latencies -> serving cycles.

This is the bridge between the repo's two halves.  The timing simulator
(:mod:`repro.sim.simulator`) knows what a page walk COSTS under each
mechanism (radix / ech / hugepage / ndpage / ideal) on a given machine;
the paged-KV serving stack (:mod:`repro.serving`) knows how often the
runtime RESOLVES translations (TranslationCache hits vs misses, and how
many PTE lines a table rebuild touches under the flat vs radix block
organization).  A :class:`TranslationCostModel` carries the per-lookup
cycle costs from the first world into the second, so ``ServeEngine``
can report tokens/sec under every mechanism — the paper's end-to-end
claim (translation design changes application throughput, §VI) at the
serving layer.

Cost derivation (:meth:`TranslationCostModel.from_sim`) is ONE
simulator dispatch: all mechanisms ride the M axis of a single
:func:`repro.sim.simulate` call on the serving machine's shape, so the
whole model costs one compile per machine shape — mechanism identity is
a value-only operand, never a recompile.  Per mechanism ``m``:

  ``tlb_hit``   cycles when the serving TranslationCache hits (the
                L1-TLB analogue): the machine's L1-DTLB latency.
  ``walk``      cycles on a miss: L2-TLB probe + the simulator's
                measured average page-table-walk latency for ``m``
                (queueing, PWC hits and cache pollution included).
  ``pte_line``  cycles per ADDITIONAL PTE cache line the rebuild
                touches beyond the first: the machine's per-line DRAM
                cost (``MachineConfig.memory.line_cycles`` — under the
                banked model a contiguous-org line streams through an
                open row, a per-node line pays the closed-row total)
                for L1-bypassing mechanisms, an L1-hit-rate-weighted
                blend for cache-filling ones.
  ``org``       which serving block-table organization the mechanism's
                line count follows: flattened mechanisms count lines of
                the contiguous flat row (adjacent leaves SHARE 64B
                lines), tree mechanisms count per-node lines (each
                directory/leaf node is its own allocation — no
                sharing), ideal counts nothing.

Derived models are memoized to the trace cache (``.trace_cache/
costmodel_<key>.json`` — same directory and degrade-to-off rules as
generated traces), and :data:`PINNED_COSTS` carries a committed
fallback table for the default ``SERVING_COST`` machine so CI's fast
lane and fresh checkouts never need a simulator run (the path is
hermetic).  Bump :data:`_COST_MODEL_VERSION` whenever the derivation
changes — it is part of the memo key.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import sys
from collections import deque
from typing import Dict, Hashable, Sequence, Tuple

import numpy as np

from repro.configs.ndp_sim import (PRESETS, SERVING_COST, MachineConfig,
                                   cpu_machine, ndp_machine)
from repro.sim import mechanisms as MS
from repro.util import resilience

#: part of the memo key: bump on any change to the derivation above
_COST_MODEL_VERSION = 3

_FACTORIES = {"ndp": ndp_machine, "cpu": cpu_machine}

#: serving-table organizations a mechanism's line count can follow
ORG_FLAT = "flat"      # one contiguous row: adjacent leaves share lines
ORG_RADIX = "radix"    # per-node allocations: directory + leaf lines
ORG_NONE = "none"      # no translation structure at all (ideal)
ORG_SEG = "segment"    # range descriptors: lines ~ contiguous runs
ORG_INV = "inverted"   # hashed buckets: every entry its own line


def serving_org(name: str) -> str:
    """Which block-table organization mechanism ``name`` maps to on the
    serving side, straight from the declarative spec registry: an
    explicit ``spec.org`` override wins (the zoo's segment/inverted
    organizations); otherwise ``flattened`` mechanisms (the NDPage
    family — with or without the L1 bypass) read the single flat row,
    everything else that walks reads a tree of independently-allocated
    nodes, and ``ideal`` reads nothing."""
    spec = MS.get(name)
    if spec.org is not None:
        return spec.org
    if spec.ideal:
        return ORG_NONE
    if spec.flattened:
        return ORG_FLAT
    return ORG_RADIX


@dataclasses.dataclass(frozen=True)
class LookupCost:
    """Per-lookup cycle costs of one mechanism (see module docstring)."""

    tlb_hit: float
    walk: float
    pte_line: float
    org: str


@dataclasses.dataclass(frozen=True)
class TranslationCostModel:
    """Per-mechanism lookup costs for one serving machine.

    ``mechs`` fixes the mechanism order every vectorized result
    follows; ``source`` records how the numbers were obtained
    ("sweep" = fresh simulator run, "cache" = trace-cache memo,
    "pinned" = the committed fallback table).
    """

    mechs: Tuple[str, ...]
    costs: Tuple[LookupCost, ...]          # aligned with mechs
    machine: str
    freq_ghz: float
    model_cycles_per_token: float
    source: str

    def cost(self, mech: str) -> LookupCost:
        return self.costs[self.mechs.index(mech)]

    @functools.cached_property
    def _vectors(self) -> Tuple[np.ndarray, ...]:
        """The per-mechanism (M,) cost arrays, materialized once — the
        meter calls :meth:`lookup_cycles` on every decode step."""
        return (np.array([c.tlb_hit for c in self.costs]),
                np.array([c.walk for c in self.costs]),
                np.array([c.pte_line for c in self.costs]),
                np.array([c.org for c in self.costs]))

    @functools.cached_property
    def needs_zoo_lines(self) -> bool:
        """True when any mechanism uses the segment/inverted accounting
        — lets the meter hot path skip those counts otherwise."""
        return any(c.org in (ORG_SEG, ORG_INV) for c in self.costs)

    # -- vectorized accounting ----------------------------------------------
    def lookup_cycles(self, hit: np.ndarray, lines_flat: np.ndarray,
                      lines_radix: np.ndarray,
                      lines_seg: np.ndarray | None = None,
                      lines_inv: np.ndarray | None = None) -> np.ndarray:
        """Translation cycles for N lookups under every mechanism.

        ``hit``: (N,) bool — the serving TranslationCache hit;
        ``lines_flat``/``lines_radix`` (and, for models carrying
        segment/inverted-org mechanisms, ``lines_seg``/``lines_inv``):
        (N,) touched-PTE-line counts of the rebuilt row under each
        organization (from ``block_table.translate_all_costed`` /
        ``count_pte_lines``).  An omitted zoo count defaults to 1 line
        (no extra-line cost).  Returns (N, M) float64.
        """
        hit = np.asarray(hit, bool)[:, None]
        lf = np.asarray(lines_flat, np.float64)[:, None]
        lr = np.asarray(lines_radix, np.float64)[:, None]
        one = np.ones_like(lf)
        ls = (one if lines_seg is None
              else np.asarray(lines_seg, np.float64)[:, None])
        li = (one if lines_inv is None
              else np.asarray(lines_inv, np.float64)[:, None])
        tlb, walk, line, org = self._vectors
        lines = np.select(
            [org == ORG_FLAT, org == ORG_RADIX, org == ORG_SEG,
             org == ORG_INV],
            [lf, lr, ls, li], default=one)
        miss = walk + line * np.maximum(lines - 1.0, 0.0)
        return np.where(hit, tlb[None], miss)

    def tokens_per_sec(self, tokens: int, trans_cycles: np.ndarray,
                       model_cycles_per_token: float | None = None
                       ) -> Dict[str, float]:
        """End-to-end throughput per mechanism: the model compute budget
        (``model_cycles_per_token`` x tokens) plus each mechanism's
        accumulated translation cycles, at the machine's clock.

        ``model_cycles_per_token`` overrides the model's own value —
        the ``serving_fleet`` benchmark re-prices the SAME accumulated
        translation cycles under a grid of compute budgets to map where
        translation stops mattering, without re-running anything."""
        if tokens <= 0:
            return {m: 0.0 for m in self.mechs}
        mcpt = (self.model_cycles_per_token
                if model_cycles_per_token is None
                else float(model_cycles_per_token))
        total = mcpt * tokens + np.asarray(trans_cycles, np.float64)
        secs = total / (self.freq_ghz * 1e9)
        return {m: float(tokens / secs[i])
                for i, m in enumerate(self.mechs)}

    # -- construction -------------------------------------------------------
    @classmethod
    def from_sim(cls, mach: MachineConfig,
                 mechs: Sequence[str] | None = None, *,
                 preset: str | None = None, workload: str | None = None,
                 model_cycles_per_token: float | None = None,
                 use_cache: bool = True) -> "TranslationCostModel":
        """Derive the cost table from ONE simulator dispatch on ``mach``.

        All mechanisms are lanes of the M axis of a single
        :func:`repro.sim.simulate` call — one compile per machine
        shape, mechanism identity is value-only.  The result is
        memoized to the trace cache keyed on everything it depends on.
        """
        mechs = tuple(mechs or SERVING_COST["mechs"])
        preset = preset or SERVING_COST["preset"]
        workload = workload or SERVING_COST["workload"]
        mcpt = float(model_cycles_per_token
                     if model_cycles_per_token is not None
                     else SERVING_COST["model_cycles_per_token"])

        path = _memo_path(mach, mechs, preset, workload)
        if use_cache:
            cached = _memo_load(path, mcpt)
            if cached is not None:
                return cached

        from repro.sim.simulator import simulate
        from repro.workloads import generate_trace
        sim_preset = PRESETS[preset]
        trace = generate_trace(workload, mach.num_cores, preset=sim_preset)
        res = simulate(mach, trace, mechs=mechs, chunk=sim_preset.chunk)

        costs = []
        for m in mechs:
            spec = MS.get(m)
            if spec.ideal:
                costs.append(LookupCost(0.0, 0.0, 0.0, ORG_NONE))
                continue
            walk = (res.scalar("avg_ptw_latency", m)
                    + float(mach.l2_tlb.latency))
            org = serving_org(m)
            # contiguous orgs stream extra lines through an open DRAM
            # row under the banked model; per-node orgs pay closed rows
            # (identical to the flat latency under bounded_linear)
            dram = mach.memory.line_cycles(
                contiguous=org in (ORG_FLAT, ORG_SEG))
            if spec.bypass_l1:
                line = dram
            else:
                l1_hit = 1.0 - res.scalar("pte_l1_miss_rate", m)
                line = (l1_hit * mach.l1d.latency
                        + (1.0 - l1_hit) * dram)
            costs.append(LookupCost(
                tlb_hit=float(mach.l1_dtlb.latency), walk=round(walk, 3),
                pte_line=round(line, 3), org=org))

        model = cls(mechs=mechs, costs=tuple(costs), machine=mach.name,
                    freq_ghz=mach.freq_ghz, model_cycles_per_token=mcpt,
                    source="sweep")
        if use_cache:
            _memo_store(path, model)
        return model

    @classmethod
    def pinned(cls, model_cycles_per_token: float | None = None
               ) -> "TranslationCostModel":
        """The committed fallback table (:data:`PINNED_COSTS`) — no
        simulator run, no cache: the hermetic path for CI fast lanes
        and fresh checkouts."""
        p = PINNED_COSTS
        mcpt = float(model_cycles_per_token
                     if model_cycles_per_token is not None
                     else SERVING_COST["model_cycles_per_token"])
        return cls(
            mechs=tuple(p["mechs"]),
            costs=tuple(LookupCost(*p["costs"][m]) for m in p["mechs"]),
            machine=p["machine"], freq_ghz=p["freq_ghz"],
            model_cycles_per_token=mcpt, source="pinned")

    @classmethod
    def for_machine(cls, mach: MachineConfig | None = None, *,
                    source: str = "sweep",
                    **kw) -> "TranslationCostModel":
        """The serving entry point.  ``source``:

        * ``"pinned"`` — the committed table, no simulation (hermetic);
        * ``"sweep"``  — derive from the simulator, serving the trace-
          cache memo when warm.  A simulator failure raises.
        """
        if source == "pinned":
            return cls.pinned(kw.get("model_cycles_per_token"))
        if source != "sweep":
            raise ValueError(f"unknown cost-model source {source!r}")
        if mach is None:
            mach = _FACTORIES[SERVING_COST["machine"]](
                int(SERVING_COST["cores"]))
        return cls.from_sim(mach, **kw)


# ---------------------------------------------------------------------------
# trace-cache memoization (same directory + degrade rules as traces)
# ---------------------------------------------------------------------------
def _engine_digest(mechs: Tuple[str, ...]) -> str:
    """Hash of everything OUTSIDE this module the derived costs depend
    on: the full spec values of the mechanisms used (walk depth, flags,
    walk-fn identity) and the simulator / page-table / trace-generator
    sources — so a mechanism, engine, or generator change can never
    silently serve a stale memo."""
    import repro.core.page_table as _pt
    import repro.sim.memory_model as _mm
    import repro.sim.simulator as _sim
    import repro.workloads.generators as _gen
    h = hashlib.sha256()
    for s in MS.specs_for(mechs):
        h.update(repr((s.name, s.n_pte, s.parallel, s.bypass_l1,
                       s.pwc_levels, s.huge, s.flattened, s.ideal,
                       s.cache_tlb, s.segment, s.colocate, s.org,
                       getattr(s.walk_fn, "__qualname__", None))
                      ).encode())
    for mod in (_sim, _pt, _gen, MS, _mm):
        with open(mod.__file__, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _memo_path(mach: MachineConfig, mechs: Tuple[str, ...], preset: str,
               workload: str) -> str | None:
    from repro.workloads import trace_cache_dir
    d = trace_cache_dir()
    if d is None:
        return None
    key_src = json.dumps({
        "machine": dataclasses.asdict(mach),
        "mechs": list(mechs), "workload": workload,
        # preset VALUES, not just the name — editing PRESETS["smoke"]
        # must re-derive
        "preset": dataclasses.asdict(PRESETS[preset]),
        "engine": _engine_digest(mechs),
        "version": _COST_MODEL_VERSION,
    }, sort_keys=True, default=str)
    h = hashlib.sha256(key_src.encode()).hexdigest()[:20]
    return os.path.join(d, f"costmodel_{mach.name}_{h}.json")


def _memo_load(path: str | None, mcpt: float
               ) -> "TranslationCostModel | None":
    """Integrity-checked memo load (sha256 sidecar, quarantine on
    corruption — see :mod:`repro.util.resilience`); None = re-derive."""
    if path is None:
        return None
    p = resilience.read_json(path)
    if p is None:
        return None
    try:
        return TranslationCostModel(
            mechs=tuple(p["mechs"]),
            costs=tuple(LookupCost(*p["costs"][m]) for m in p["mechs"]),
            machine=p["machine"], freq_ghz=p["freq_ghz"],
            model_cycles_per_token=mcpt, source="cache")
    except Exception:                    # schema drift: re-derive
        resilience.quarantine(path, "costmodel memo schema mismatch")
        return None


def _memo_store(path: str | None, model: TranslationCostModel) -> None:
    if path is None:
        return
    # atomic + sidecar; filesystem failure degrades to cache-off
    resilience.write_json(path, {
        "mechs": list(model.mechs),
        "costs": {m: list(dataclasses.astuple(c))
                  for m, c in zip(model.mechs, model.costs)},
        "machine": model.machine, "freq_ghz": model.freq_ghz,
    }, indent=1)


# ---------------------------------------------------------------------------
# the committed fallback table
# ---------------------------------------------------------------------------
#: Derived once via ``TranslationCostModel.from_sim(ndp_machine(4))`` on
#: the SERVING_COST defaults (dlrm workload, smoke preset) and pinned so
#: the serving path never NEEDS a simulator run.  Regenerate with
#: ``python -m repro.sim.cost_model`` after changing the derivation or
#: the SERVING_COST preset (tests/test_cost_model.py asserts the pinned
#: and freshly-derived tables agree).
PINNED_COSTS: Dict = {
    "machine": "ndp-4c",
    "freq_ghz": 2.6,
    "mechs": ("radix", "ech", "hugepage", "ndpage", "ideal"),
    "costs": {
        # (tlb_hit, walk, pte_line, org)
        "radix": (1.0, 482.827, 90.628, ORG_RADIX),
        "ech": (1.0, 343.52, 100.0, ORG_RADIX),
        "hugepage": (1.0, 300.021, 92.463, ORG_RADIX),
        "ndpage": (1.0, 290.523, 100.0, ORG_FLAT),
        "ideal": (0.0, 0.0, 0.0, ORG_NONE),
    },
}


# ---------------------------------------------------------------------------
# the serving-side accumulator
# ---------------------------------------------------------------------------
class TranslationMeter:
    """Accumulates translation cycles per mechanism as the serving
    scheduler resolves lookups — the per-step and per-request budget
    ``ServeEngine`` reports throughput from.

    One meter serves EVERY mechanism at once: the engine runs a single
    decode loop (one compile, mechanism never enters the jit) and each
    step's cache hits / misses / touched-line counts are priced under
    all mechanisms simultaneously.
    """

    #: bounded histories, so a long-lived engine never grows without
    #: limit (running totals are exact regardless): per-step cycle
    #: vectors, and budgets of RETIRED requests.  Live requests are
    #: bounded by the scheduler's batch size.
    STEP_HISTORY = 4096
    RETIRED_HISTORY = 4096

    def __init__(self, model: TranslationCostModel,
                 max_slots: int | None = None):
        self.model = model
        m = len(model.mechs)
        self.total = np.zeros(m, np.float64)
        self.step_cycles: "deque[np.ndarray]" = deque(
            maxlen=self.STEP_HISTORY)                  # per-step (M,)
        #: live per-request budgets (seq_id -> (M,) cycles)
        self.per_request: Dict[Hashable, np.ndarray] = {}
        #: budgets of completed requests, most recent last
        self.retired: "deque[Tuple[Hashable, np.ndarray]]" = deque(
            maxlen=self.RETIRED_HISTORY)
        self.tokens = 0
        self.steps = 0
        self.hits = 0
        self.misses = 0
        # -- the vectorized slot path (fleet scheduler) ---------------------
        # per-slot live budgets as one (max_slots, M) matrix accumulated
        # array-at-once by record_slots; budgets flush into the
        # per_request / retired dicts only at release time, so NO
        # per-request Python loop runs on the step path.
        self._slot_budget = (np.zeros((max_slots, m), np.float64)
                             if max_slots else None)
        self._slot_owner: list = [None] * (max_slots or 0)

    def record_step(self, seq_ids: Sequence[Hashable], hit: np.ndarray,
                    flat_rows: np.ndarray, leaf_size: int) -> None:
        """Price one scheduler step.  ``flat_rows`` is the (N, max_pages)
        int32 mapping the step resolved (-1 holes).  Line counts are
        computed in plain numpy (no device dispatch on the decode hot
        path) and only for MISS rows — hits are priced at tlb_hit and
        never read them; tests pin the numpy path against the canonical
        ``block_table.count_pte_lines``."""
        n = len(seq_ids)
        if n == 0:
            return
        hit = np.asarray(hit, bool)
        flat = np.asarray(flat_rows, np.int32)
        lf = np.ones(n, np.int64)
        lr = np.ones(n, np.int64)
        lseg = np.ones(n, np.int64)
        linv = np.ones(n, np.int64)
        miss = np.flatnonzero(~hit)
        if miss.size:
            ls = _usable_leaf_size(flat.shape[1], leaf_size)
            lf[miss], lr[miss] = _np_row_lines(flat[miss], ls)
            if self.model.needs_zoo_lines:
                lseg[miss] = _np_seg_lines(flat[miss])
                linv[miss] = _np_inv_lines(flat[miss])
        per_seq = self.model.lookup_cycles(hit, lf, lr, lseg, linv)
        for i, sid in enumerate(seq_ids):
            if sid in self.per_request:
                self.per_request[sid] = self.per_request[sid] + per_seq[i]
            else:
                self.per_request[sid] = per_seq[i].copy()
        step = per_seq.sum(axis=0)
        self.step_cycles.append(step)
        self.total += step
        self.tokens += n                  # every active slot advances one
        self.steps += 1
        h = int(hit.sum())
        self.hits += h
        self.misses += n - h

    # -- the vectorized slot path (fleet scheduler) --------------------------
    def bind_slot(self, slot: int, req_id: Hashable) -> None:
        """Attach ``req_id`` to a scheduler slot (admission).  Requires
        the meter was built with ``max_slots``."""
        assert self._slot_budget is not None, "meter built without slots"
        assert self._slot_owner[slot] is None, (slot, req_id)
        self._slot_owner[slot] = req_id

    def record_slots(self, slots: np.ndarray, hit: np.ndarray,
                     flat_rows: np.ndarray, leaf_size: int, *,
                     shared_leaves: bool = False) -> None:
        """Vectorized :meth:`record_step` over scheduler SLOTS: prices
        one fleet step for every active slot with no per-request Python
        loop — line counts, per-mechanism cycles and the per-slot budget
        accumulation are all array-at-once.  ``shared_leaves=True``
        (prefix-sharing mixes) counts radix-org lines with batch-global
        shared-leaf dedup (:func:`_np_row_lines_shared`): a leaf walked
        by several missing sharers in the same step costs its lines
        once."""
        assert self._slot_budget is not None, "meter built without slots"
        slots = np.asarray(slots, np.int64)
        n = slots.size
        if n == 0:
            return
        hit = np.asarray(hit, bool)
        flat = np.asarray(flat_rows, np.int32)
        lf = np.ones(n, np.int64)
        lr = np.ones(n, np.int64)
        lseg = np.ones(n, np.int64)
        linv = np.ones(n, np.int64)
        miss = np.flatnonzero(~hit)
        if miss.size:
            ls = _usable_leaf_size(flat.shape[1], leaf_size)
            rows = flat[miss]
            if shared_leaves:
                lf[miss], lr[miss] = _np_row_lines_shared(rows, ls)
            else:
                lf[miss], lr[miss] = _np_row_lines(rows, ls)
            if self.model.needs_zoo_lines:
                lseg[miss] = _np_seg_lines(rows)
                linv[miss] = _np_inv_lines(rows)
        per_seq = self.model.lookup_cycles(hit, lf, lr, lseg, linv)
        self._slot_budget[slots] += per_seq      # slots are unique
        step = per_seq.sum(axis=0)
        self.step_cycles.append(step)
        self.total += step
        self.tokens += n
        self.steps += 1
        h = int(hit.sum())
        self.hits += h
        self.misses += n - h

    def release_slot(self, slot: int, *, retire: bool) -> None:
        """Fold a slot's accumulated budget into its request's dict
        entry (preemption keeps it live — re-prefill work accumulates
        across incarnations; ``retire=True`` moves it to the bounded
        retired history)."""
        assert self._slot_budget is not None, "meter built without slots"
        req_id = self._slot_owner[slot]
        assert req_id is not None, slot
        self._slot_owner[slot] = None
        budget = self._slot_budget[slot].copy()
        self._slot_budget[slot] = 0.0
        if req_id in self.per_request:
            self.per_request[req_id] = self.per_request[req_id] + budget
        else:
            self.per_request[req_id] = budget
        if retire:
            self.retire_request(req_id)

    def retire_request(self, seq_id: Hashable) -> None:
        """Move a completed request's budget out of the live dict (kept
        in the bounded ``retired`` history) — called by the scheduler
        when it frees the sequence, so the live dict stays bounded by
        the batch size."""
        budget = self.per_request.pop(seq_id, None)
        if budget is not None:
            self.retired.append((seq_id, budget))

    def request_budgets(self) -> Dict[Hashable, np.ndarray]:
        """Live AND retained-retired per-request budgets (retired
        entries beyond the history window are folded into ``total``
        only).  A recycled request id SUMS across its incarnations —
        the partition over ``total`` survives id reuse."""
        out: Dict[Hashable, np.ndarray] = {}
        live_slots = (
            [(rid, self._slot_budget[s])
             for s, rid in enumerate(self._slot_owner) if rid is not None]
            if self._slot_budget is not None else [])
        for sid, budget in (list(self.retired)
                            + list(self.per_request.items()) + live_slots):
            if sid in out:
                out[sid] = out[sid] + budget
            else:
                out[sid] = budget.copy()
        return out

    def tokens_per_sec(self, model_cycles_per_token: float | None = None
                       ) -> Dict[str, float]:
        return self.model.tokens_per_sec(self.tokens, self.total,
                                         model_cycles_per_token)

    def translation_cycles(self) -> Dict[str, float]:
        return {m: float(self.total[i])
                for i, m in enumerate(self.model.mechs)}

    def per_step_cycles(self) -> Dict[str, Dict[str, float]]:
        """The per-step translation budget over the retained step
        window: mean and worst-case (miss-heavy) step cycles per
        mechanism."""
        if not self.step_cycles:
            return {m: {"mean": 0.0, "max": 0.0}
                    for m in self.model.mechs}
        steps = np.stack(self.step_cycles)            # (S, M)
        return {m: {"mean": float(steps[:, i].mean()),
                    "max": float(steps[:, i].max())}
                for i, m in enumerate(self.model.mechs)}


def _usable_leaf_size(max_pages: int, leaf_size: int) -> int:
    """Largest leaf size <= requested that divides ``max_pages`` (the
    radix builder requires an exact split)."""
    ls = max(1, min(leaf_size, max_pages))
    while max_pages % ls:
        ls -= 1
    return ls


def _np_group_lines(mapped: np.ndarray) -> np.ndarray:
    """Numpy twin of ``block_table._lines_of`` (same PTE_PER_LINE
    granularity, pinned equal by tests): touched line groups of a
    line-aligned span, over the last axis."""
    from repro.core.block_table import PTE_PER_LINE
    n = mapped.shape[-1]
    pad = (-n) % PTE_PER_LINE
    m = np.pad(mapped, [(0, 0)] * (mapped.ndim - 1) + [(0, pad)])
    groups = m.reshape(m.shape[:-1] + (-1, PTE_PER_LINE))
    return groups.any(-1).sum(-1)


def _np_row_lines(flat: np.ndarray, leaf_size: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Touched-PTE-line counts of (N, max_pages) mapping rows under the
    flat and the radix organization, pure numpy — the decode-hot-path
    equivalent of ``count_pte_lines(flat/radix_from_flat(flat))`` for
    the unique-leaf tables the scheduler builds."""
    mapped = flat >= 0                                # (N, maxp)
    lf = _np_group_lines(mapped)
    n, maxp = mapped.shape
    leaves = mapped.reshape(n, maxp // leaf_size, leaf_size)
    dir_valid = leaves.any(-1)                        # (N, n_dir)
    lr = _np_group_lines(dir_valid) + _np_group_lines(leaves).sum(-1)
    return lf, lr


def _np_row_lines_shared(flat: np.ndarray, leaf_size: int
                         ) -> Tuple[np.ndarray, np.ndarray]:
    """:func:`_np_row_lines` with BATCH-GLOBAL shared-leaf dedup on the
    radix count — the numpy hot-path twin of
    ``block_table.count_pte_lines_shared`` (pinned equal by tests).

    A leaf whose physical-page content is identical across rows (a
    prefix-shared system prompt) is one allocation: its lines are
    charged to the FIRST row (row-major) referencing it and zero to
    every other sharer.  The flat count is unchanged — each flat row is
    its own contiguous allocation, so prefix sharing buys it nothing
    (NDPage's tradeoff, surfaced end-to-end).
    """
    mapped = flat >= 0                                # (N, maxp)
    lf = _np_group_lines(mapped)
    n, maxp = mapped.shape
    n_dir = maxp // leaf_size
    leaves = flat.reshape(n * n_dir, leaf_size)
    lmapped = mapped.reshape(n * n_dir, leaf_size)
    valid = lmapped.any(-1)
    lines = np.zeros(n * n_dir, np.int64)
    vidx = np.flatnonzero(valid)
    if vidx.size:
        sub = leaves[vidx]
        # deterministic first occurrence of each distinct leaf content
        # (np.unique's return_index is not guaranteed first-occurrence
        # for axis-based unique)
        _, inverse = np.unique(sub, axis=0, return_inverse=True)
        inverse = inverse.reshape(-1)
        first = np.full(int(inverse.max()) + 1, vidx.size, np.int64)
        np.minimum.at(first, inverse, np.arange(vidx.size))
        keep = vidx[first]
        lines[keep] = _np_group_lines(lmapped[keep])
    dir_valid = valid.reshape(n, n_dir)
    lr = _np_group_lines(dir_valid) + lines.reshape(n, n_dir).sum(-1)
    return lf, lr


def _np_seg_lines(flat: np.ndarray) -> np.ndarray:
    """Numpy twin of ``block_table.count_segment_lines`` (pinned equal
    by tests): descriptor lines for the SEGMENT org — one range per
    maximal physically-contiguous mapped run, RANGES_PER_LINE per
    line."""
    from repro.core.block_table import RANGES_PER_LINE
    flat = np.asarray(flat, np.int64)
    mapped = flat >= 0
    nd = flat.ndim
    pad_cfg = [(0, 0)] * (nd - 1) + [(1, 0)]
    prev_m = np.pad(mapped[..., :-1], pad_cfg, constant_values=False)
    prev_p = np.pad(flat[..., :-1], pad_cfg, constant_values=-2)
    runs = (mapped & (~prev_m | (flat != prev_p + 1))).sum(-1)
    return (runs + RANGES_PER_LINE - 1) // RANGES_PER_LINE


def _np_inv_lines(flat: np.ndarray) -> np.ndarray:
    """Numpy twin of ``block_table.count_inverted_lines``: every mapped
    entry hashes to its own bucket line — no sharing, ever."""
    return (np.asarray(flat, np.int64) >= 0).sum(-1)


def _main() -> int:                     # pragma: no cover - dev utility
    """Regenerate :data:`PINNED_COSTS` from the SERVING_COST defaults."""
    mach = _FACTORIES[SERVING_COST["machine"]](int(SERVING_COST["cores"]))
    model = TranslationCostModel.from_sim(mach, use_cache=False)
    print(json.dumps({
        "machine": model.machine, "freq_ghz": model.freq_ghz,
        "mechs": model.mechs,
        "costs": {m: dataclasses.astuple(c)
                  for m, c in zip(model.mechs, model.costs)},
    }, indent=1, default=str))
    return 0


if __name__ == "__main__":              # pragma: no cover
    sys.exit(_main())

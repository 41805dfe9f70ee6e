"""Plain reference of the NDP address-translation timing model, in numpy.

It implements the model that NDPage's evaluation describes, from the
configuration file alone, with none of the simulator's code: per core
and mechanism, an L1 DTLB, an L2 TLB, per-level page-walk caches and
the data-cache hierarchy are set-associative LRU tables; an access that
misses the TLBs walks the mechanism's page table; PTE lines go through
the cache hierarchy, or straight to memory where the mechanism bypasses
it; memory pays its latency plus a bounded-linear queueing delay taken
from the demand measured so far, held for a chunk of accesses.

``simulate`` returns per lane (one workload's trace) the counters and
cycles of every (mechanism, core).  ``cycle_dtype`` is the precision of
all cycle arithmetic: float64 for the reference, bfloat16 for the
control that ``bench/calibrate.py`` runs.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

MAX_PTE = 4
HUGE_SHIFT = 9
PT_REGION_LINE = 1 << 28
PTES_PER_LINE = 8
NODE_LINES = 64
FLAT_LINES = (1 << 18) // PTES_PER_LINE
LEVEL_SHIFTS = (27, 18, 9, 0)          # L4, L3, L2, L1 index bits

COUNTERS = ("walks", "l1tlb_misses", "pte_accesses", "pte_l1_hits",
            "pte_mem", "data_l1_misses", "data_mem")
CYCLES = ("cycles", "trans_cycles", "walk_cycles")


# ---------------------------------------------------------------------------
# page-table walks: the PTE line ids a walk touches
# ---------------------------------------------------------------------------
def _mix(x, salt: int) -> np.ndarray:
    x = np.asarray(x).astype(np.uint32) ^ np.uint32(salt)
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def _node_line(vpn, shift: int, salt: int) -> np.ndarray:
    """Line of the PTE indexing ``vpn`` in a 4KB node placed at a hash of
    the VPN prefix above the level."""
    idx = (vpn >> shift) & 511
    base = (_mix(vpn >> (shift + 9), salt) & np.uint32(0xFFFFF)).astype(
        np.int64) * NODE_LINES
    return PT_REGION_LINE + base + idx // PTES_PER_LINE


def walk_radix(vpn):
    return [_node_line(vpn, sh, 0xA0 + i) for i, sh in enumerate(LEVEL_SHIFTS)]


def walk_ndpage(vpn):
    """L4, L3, then one access into the flattened 2MB L2/L1 node."""
    base = (_mix(vpn >> 18, 0xF1) & np.uint32(0x3F)).astype(np.int64)
    flat = PT_REGION_LINE + base * FLAT_LINES + (vpn & ((1 << 18) - 1)) // 8
    return [_node_line(vpn, 27, 0xA0), _node_line(vpn, 18, 0xA1), flat]


def walk_hugepage(vpn):
    return [_node_line(vpn, sh, 0xB0 + i)
            for i, sh in enumerate(LEVEL_SHIFTS[:3])]


def walk_ech(vpn):
    """Two hashed cuckoo probes, each into a table of 2^24 lines."""
    return [PT_REGION_LINE + (1 << 24) * (w + 1)
            + (_mix(vpn, 0xC0 + w) & np.uint32(0xFFFFFF)).astype(np.int64)
            for w in range(2)]


WALKS = {"radix": walk_radix, "ndpage": walk_ndpage,
         "hugepage": walk_hugepage, "ech": walk_ech, "none": lambda v: []}


def _fragmented(vpn, frac: float) -> np.ndarray:
    """2MB regions that fell back to 4KB mappings."""
    x = (np.asarray(vpn) >> HUGE_SHIFT).astype(np.uint32) ^ np.uint32(
        0x9E3779B9)
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x7FEB352D)
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x846CA68B)
    x = x ^ (x >> np.uint32(16))
    return (x % np.uint32(1000)) < int(frac * 1000)


# ---------------------------------------------------------------------------
# LRU tables
# ---------------------------------------------------------------------------
class LRU:
    """``lanes`` independent set-associative tables.  A way's stamp is the
    time of its last touch; empty ways hold tag 0 and stamp 0, and a miss
    fills the first way of least stamp."""

    def __init__(self, lanes: int, sets: int, ways: int):
        self.sets = sets
        self.tags = np.zeros((lanes, sets, ways), np.int64)
        self.stamp = np.zeros((lanes, sets, ways), np.int64)

    def access(self, key, en, stamp, set_index=None) -> np.ndarray:
        hit = np.zeros(en.shape, bool)
        lane = np.nonzero(en)[0]
        if lane.size == 0:
            return hit
        key = key[lane]
        if set_index is None:
            s, tag = key % self.sets, key // self.sets + 1
        else:
            s, tag = np.full(lane.shape, set_index), key + 1
        row_tags = self.tags[lane, s]
        match = row_tags == tag[:, None]
        hit[lane] = match.any(axis=1)
        way = np.argmin(np.where(match, np.iinfo(np.int64).min,
                                 self.stamp[lane, s]), axis=1)
        self.tags[lane, s, way] = tag
        self.stamp[lane, s, way] = stamp[lane]
        return hit


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def simulate(machine: Dict, mechanisms: Sequence[Dict],
             traces: Sequence[Dict[str, np.ndarray]], chunk: int,
             cycle_dtype=np.float64) -> List[Dict[str, np.ndarray]]:
    """Counters and cycles of every lane; arrays are (mechanisms, cores).

    The LRU tables evolve access by access; the latencies of a chunk are
    then summed with that chunk's queueing delay."""
    ct = cycle_dtype
    b = len(traces)
    c = machine["num_cores"]
    m = len(mechanisms)
    n = b * c * m
    t_len = traces[0]["vpn"].shape[1]
    if any(tr["vpn"].shape != (c, t_len) for tr in traces):
        raise ValueError("every lane needs a (cores, length) trace of one "
                         "length")

    def per_lane(values):              # (m,) -> (n,); lane = (b, c, m)
        return np.tile(np.asarray(values), b * c)

    n_pte = per_lane([x["n_pte"] for x in mechanisms])
    parallel = per_lane([x["parallel"] for x in mechanisms])
    bypass = per_lane([x["bypass_l1"] for x in mechanisms])
    huge = per_lane([x["huge"] for x in mechanisms])
    ideal = per_lane([x["ideal"] for x in mechanisms])
    pwc_on = np.stack([per_lane([x["pwc_levels"][lv] for x in mechanisms])
                       for lv in range(MAX_PTE)], -1)

    hier = [machine["l1d"]] + [machine[k] for k in ("l2", "l3")
                               if machine.get(k)]
    tlb1, tlb2 = machine["l1_dtlb"], machine["l2_tlb"]
    l1tlb = LRU(n, tlb1["entries"] // tlb1["ways"], tlb1["ways"])
    l2tlb = LRU(n, tlb2["entries"] // tlb2["ways"], tlb2["ways"])
    pwc = LRU(n, MAX_PTE, machine["pwc_entries"])
    caches = [LRU(n, p["size_bytes"] // (p["ways"] * 64), p["ways"])
              for p in hier]
    slots = 2 + MAX_PTE + 5 * len(hier)

    mem = machine["memory"]
    lat_mem, service = ct(mem["latency"]), ct(mem["service"])
    queue_k, rho_max = ct(mem["queue_k"]), ct(mem["rho_max"])
    hier_lat = [ct(p["latency"]) for p in hier]
    l2tlb_lat = ct(tlb2["latency"])
    pwc_lat = ct(machine["pwc_latency"])
    promo = ct(machine["hugepage_stall_cycles"])
    rehash = ct(machine["ech_rehash_cycles"])

    def lanes(a):                      # (b, c, T) -> (T, n)
        return np.repeat(a.reshape(b * c, -1), m, axis=0).T

    vpn_all = lanes(np.stack([tr["vpn"] for tr in traces]).astype(np.int64))
    off_all = lanes(np.stack([tr["off"] for tr in traces]).astype(np.int64))
    work_all = lanes(np.stack([tr["work"] for tr in traces])).astype(ct)
    frag_all = _fragmented(vpn_all, machine["fragmented_4k_share"])

    def fold(a):                       # (n,) -> (b, m, c)
        return a.reshape(b, c, m).transpose(0, 2, 1)

    clock = np.zeros((b, m, c), ct)
    mem_accs = np.zeros((b, m), ct)
    counts = {k: np.zeros((b, m, c), np.int64) for k in COUNTERS}
    trans_sum = np.zeros((b, m, c), ct)
    walk_sum = np.zeros((b, m, c), ct)
    for t0 in range(0, t_len, chunk):
        t1 = min(t0 + chunk, t_len)
        vpn, frag = vpn_all[t0:t1], frag_all[t0:t1]
        k = t1 - t0
        # the PTE lines of every mechanism's walk; huge-page mechanisms
        # walk the radix table where the region fell back to 4KB pages
        lines = np.zeros((k, n, MAX_PTE), np.int64)
        radix = np.stack(walk_radix(vpn), -1)
        for j, x in enumerate(mechanisms):
            wl = WALKS[x["walk"]](vpn[:, j::m])
            for lv, line in enumerate(wl):
                lines[:, j::m, lv] = line
            if x["huge"]:
                lines[:, j::m] = np.where(frag[:, j::m, None],
                                          radix[:, j::m], lines[:, j::m])
        depth = np.where(huge & frag, MAX_PTE, n_pte)
        tlb_key = np.where(huge & ~frag, (vpn >> HUGE_SHIFT) | (1 << 26), vpn)
        data_line = vpn * 64 + off_all[t0:t1]

        # --- LRU state, access by access ----------------------------------
        en0 = ~ideal
        h1 = np.zeros((k, n), bool)
        h2 = np.zeros((k, n), bool)
        pwc_hit = np.zeros((k, n, MAX_PTE), bool)
        hits = np.zeros((len(hier), k, n, 5), bool)
        for i, t in enumerate(range(t0, t1)):
            stamp = np.full(n, t * slots, np.int64)
            h1[i] = l1tlb.access(tlb_key[i], en0, stamp)
            en1 = en0 & ~h1[i]
            h2[i] = l2tlb.access(tlb_key[i], en1, stamp + 1)
            walk = en1 & ~h2[i]
            need = walk[:, None] & (np.arange(MAX_PTE)[None] < depth[i][:, None])
            for lv in range(MAX_PTE):
                pwc_hit[i, :, lv] = pwc.access(
                    lines[i, :, lv], need[:, lv] & pwc_on[:, lv],
                    stamp + 2 + lv, set_index=lv)
            need &= ~pwc_hit[i]
            sites = [lines[i, :, lv] for lv in range(MAX_PTE)] + [data_line[i]]
            ens = [need[:, lv] & ~bypass for lv in range(MAX_PTE)]
            ens.append(np.ones(n, bool))
            for h, cache in enumerate(caches):
                base = stamp + 2 + MAX_PTE + 5 * h
                for s in range(5):
                    hits[h, i, :, s] = cache.access(sites[s], ens[s], base + s)
                    ens[s] = ens[s] & ~hits[h, i, :, s]

        # --- latencies of the chunk ----------------------------------------
        elapsed = np.maximum(clock.mean(axis=2), ct(1))            # (b, m)
        rho = np.clip(mem_accs / elapsed * service, ct(0), rho_max)
        q = np.tile((service * rho * queue_k).astype(ct)[:, None, :],
                    (1, c, 1)).reshape(n)
        mem_cost = (lat_mem + q).astype(ct)                         # (n,)
        walk = en0 & ~h1 & ~h2                                      # (k, n)
        pte_en = walk[..., None] & (np.arange(MAX_PTE) < depth[..., None])
        need = pte_en & ~pwc_hit
        lat = np.zeros((k, n, 5), ct)
        reached = np.ones((k, n, 5), bool)
        for h in range(len(hier)):
            lat = (lat + np.where(reached, hier_lat[h], ct(0))).astype(ct)
            reached &= ~hits[h]
        lat = (lat + np.where(reached, mem_cost[:, None], ct(0))).astype(ct)
        pte_lat = np.where(bypass[:, None], mem_cost[:, None],
                           lat[..., :MAX_PTE])
        pte_lat = np.where(pwc_hit, pwc_lat, pte_lat)
        pte_lat = np.where(pte_en, pte_lat, ct(0)).astype(ct)
        walk_cyc = np.where(parallel, pte_lat.max(axis=-1) + ct(2) + rehash,
                            pte_lat.sum(axis=-1, dtype=ct)).astype(ct)
        trans = np.where(walk, walk_cyc, ct(0))
        trans = np.where(en0 & ~h1, l2tlb_lat + trans, ct(0))
        trans = (trans + np.where(huge, promo, ct(0))).astype(ct)
        pte_mem = need & (bypass[:, None] | reached[..., :MAX_PTE])
        step = (work_all[t0:t1] + ct(1) + trans
                + (lat[..., MAX_PTE] - hier_lat[0])).astype(ct)

        clock = (clock + fold(step.sum(axis=0, dtype=ct))).astype(ct)
        mem_n = pte_mem.sum(axis=(0, 2)) + reached[..., MAX_PTE].sum(axis=0)
        mem_accs = (mem_accs + fold(mem_n.astype(ct)).sum(axis=2,
                                                          dtype=ct)).astype(ct)
        trans_sum = (trans_sum + fold(trans.sum(axis=0, dtype=ct))).astype(ct)
        walk_sum = (walk_sum + fold(np.where(walk, walk_cyc, ct(0)).sum(
            axis=0, dtype=ct))).astype(ct)
        chunk_counts = {
            "walks": walk.sum(axis=0),
            "l1tlb_misses": (en0 & ~h1).sum(axis=0),
            "pte_accesses": need.sum(axis=(0, 2)),
            "pte_l1_hits": hits[0, ..., :MAX_PTE].sum(axis=(0, 2)),
            "pte_mem": pte_mem.sum(axis=(0, 2)),
            "data_l1_misses": (~hits[0, ..., MAX_PTE]).sum(axis=0),
            "data_mem": reached[..., MAX_PTE].sum(axis=0),
        }
        for key, v in chunk_counts.items():
            counts[key] += fold(v)

    return [dict({key: counts[key][i] for key in COUNTERS},
                 cycles=clock[i], trans_cycles=trans_sum[i],
                 walk_cycles=walk_sum[i]) for i in range(b)]

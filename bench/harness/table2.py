"""Synthetic memory traces of the Table-II workloads, generated from a seed.

A copy of the simulator's own trace generators, kept with the benchmark
so that a change to the program's generators cannot move the yardstick.
``bench/tests/test_traffic.py`` pins this copy to the program's output.

Each generator emits, for all cores at once, ``(vpn, line_offset, work)``:
the 4KB virtual page, the 64B line within it, and the non-memory
instructions that precede the access.  Footprints follow Table II
unscaled; only the trace window is short.
"""
from __future__ import annotations

import zlib
from typing import Dict, List

import numpy as np

PAGE_LINES = 64  # 4KB / 64B


def _pages(footprint_gb: float) -> int:
    return max(1 << 14, int(footprint_gb * (1 << 18)))


def _hot_lines(rng, shape, pages: int, alpha: float) -> np.ndarray:
    """Power-law line accesses over contiguous hot ids."""
    total = pages * PAGE_LINES
    u = rng.random(shape)
    x = np.floor(total * u ** alpha).astype(np.int64)
    return np.minimum(x, total - 1)


def _runs(rng, cores: int, n: int, pages: int, run_len: int,
          rep: int = 6) -> np.ndarray:
    """Sequential runs: each line touched ``rep`` times in a row."""
    n_lines = max(1, n // (run_len * rep)) * run_len
    starts = rng.integers(0, pages,
                          (cores, max(1, n_lines // run_len))) * PAGE_LINES
    offs = np.arange(run_len)
    lines = (starts[..., None] + offs[None, None, :]).reshape(cores, -1)
    lines = np.repeat(lines, rep, axis=1)[:, :n]
    if lines.shape[1] < n:
        lines = np.pad(lines, ((0, 0), (0, n - lines.shape[1])), mode="wrap")
    return lines % (pages * PAGE_LINES)


def _mix_streams(rng, parts, weights, n: int) -> np.ndarray:
    """Interleave streams by weight, consuming each in order per core."""
    cores = parts[0].shape[0]
    choice = rng.choice(len(parts), size=(cores, n),
                        p=np.asarray(weights) / np.sum(weights))
    out = np.empty((cores, n), np.int64)
    for i, p in enumerate(parts):
        mask = choice == i
        take = (np.cumsum(mask, axis=1) - 1) % p.shape[1]
        vals = np.take_along_axis(np.ascontiguousarray(p), take, axis=1)
        out[mask] = vals[mask]
    return out


def _emit(lines: np.ndarray, work: np.ndarray):
    vpn = (lines // PAGE_LINES).astype(np.int32)
    off = (lines % PAGE_LINES).astype(np.int32)
    return vpn, off, work.astype(np.int32)


def _hash32(x):
    x = np.asarray(x, np.uint32) ^ np.uint32(0x9E3779B9)
    x = (x ^ (x >> 16)) * np.uint32(0x7FEB352D)
    return (x ^ (x >> 15)).astype(np.int64)


def gen_uniform(rng, cores, n, pages):
    lines = rng.integers(0, pages * PAGE_LINES, (cores, n))
    work = rng.integers(1, 4, (cores, n))
    return _emit(lines, work)


def gen_graph(rng, cores, n, pages, alpha=2.1):
    hot = _hot_lines(rng, (cores, n), pages, 2 * alpha)
    seq = _runs(rng, cores, n, pages, run_len=8, rep=8)
    cold = rng.integers(0, pages * PAGE_LINES, (cores, n))
    lines = _mix_streams(rng, [hot, seq, cold], [0.5, 0.35, 0.15], n)
    work = rng.integers(2, 7, (cores, n))
    return _emit(lines, work)


def gen_graph_frontier(rng, cores, n, pages, alpha=2.1):
    frontier = _runs(rng, cores, n, pages, run_len=32, rep=8)
    expand = _hot_lines(rng, (cores, n), pages, 2 * alpha)
    cold = rng.integers(0, pages * PAGE_LINES, (cores, n))
    lines = _mix_streams(rng, [frontier, expand, cold], [0.45, 0.35, 0.2], n)
    work = rng.integers(2, 6, (cores, n))
    return _emit(lines, work)


def gen_graph_sweep(rng, cores, n, pages, alpha=2.1):
    sweep = np.broadcast_to(
        np.repeat(np.arange(n // 8 + 1), 8)[:n] % (pages * PAGE_LINES),
        (cores, n))
    edges = rng.integers(0, pages * PAGE_LINES, (cores, n))
    hot = _hot_lines(rng, (cores, n), pages, 2 * alpha)
    lines = _mix_streams(rng, [sweep, edges, hot], [0.5, 0.25, 0.25], n)
    work = rng.integers(2, 5, (cores, n))
    return _emit(lines, work)


def gen_mc_lookup(rng, cores, n, pages):
    """XSBench: a binary-search ladder over grid pages, then a short
    sequential read of the nuclide data."""
    ladder = 6
    read = 6
    n_look = max(1, n // (ladder + read))
    centers = rng.integers(0, pages, (cores, n_look))
    cols = []
    for step in range(ladder):
        stride = max(pages >> (step + 1), 1)
        if step < 3:
            node = (pages >> 1) // max(stride, 1) * stride % pages
            jitter = np.full((cores, n_look), node)
        else:
            jitter = ((centers + (rng.integers(0, 2, (cores, n_look)) * 2 - 1)
                       * stride) % pages)
        cols.append(jitter * PAGE_LINES + (_hash32(jitter) % PAGE_LINES))
    hit_line = centers * PAGE_LINES + rng.integers(0, PAGE_LINES,
                                                   (cores, n_look))
    for r in range(read):
        cols.append(hit_line + (r // 3))
    lines = np.stack(cols, axis=2).reshape(cores, -1)[:, :n]
    if lines.shape[1] < n:
        lines = np.pad(lines, ((0, 0), (0, n - lines.shape[1])), mode="wrap")
    work = rng.integers(4, 9, (cores, n))
    return _emit(lines, work)


def gen_embedding_bag(rng, cores, n, pages):
    """DLRM: bags of random rows plus a dense sequential MLP segment."""
    rows = _hot_lines(rng, (cores, n), pages, alpha=2.2)
    rows = np.repeat(rows[:, : max(1, n // 4)], 4, axis=1)[:, :n]
    dense = _runs(rng, cores, n, max(pages // 64, 1), run_len=64, rep=8)
    lines = _mix_streams(rng, [rows, dense], [0.6, 0.4], n)
    work = rng.integers(1, 4, (cores, n))
    return _emit(lines, work)


def gen_kmer(rng, cores, n, pages):
    probes = rng.integers(0, pages * PAGE_LINES, (cores, n))
    probes = np.repeat(probes[:, : max(1, n // 3)], 3, axis=1)[:, :n]
    runs = _runs(rng, cores, n, pages, run_len=4, rep=8)
    lines = _mix_streams(rng, [probes, runs], [0.55, 0.45], n)
    work = rng.integers(2, 6, (cores, n))
    return _emit(lines, work)


PATTERNS = {
    "uniform": gen_uniform,
    "graph": gen_graph,
    "graph_frontier": gen_graph_frontier,
    "graph_sweep": gen_graph_sweep,
    "mc_lookup": gen_mc_lookup,
    "embedding_bag": gen_embedding_bag,
    "kmer": gen_kmer,
}


def generate(name: str, spec: Dict, cores: int, length: int,
             seed: int) -> Dict[str, np.ndarray]:
    """One workload's per-core trace: ``vpn``/``off``/``work`` arrays of
    shape ``(cores, length)``, from ``seed`` alone."""
    pattern = PATTERNS[spec["pattern"]]
    pages = _pages(spec["footprint_gb"])
    rng = np.random.default_rng([seed, zlib.crc32(name.encode()), cores])
    kwargs = {}
    if "alpha" in spec and "alpha" in pattern.__code__.co_varnames:
        kwargs["alpha"] = spec["alpha"]
    vpn, off, work = pattern(rng, cores, length, pages, **kwargs)
    return {"vpn": vpn, "off": off, "work": work, "pages": pages}


def generate_suite(workloads: Dict[str, Dict], cores: int, length: int,
                   seed: int) -> List[Dict[str, np.ndarray]]:
    """Traces of every workload of a suite, in the suite's order."""
    return [generate(n, s, cores, length, seed) for n, s in workloads.items()]

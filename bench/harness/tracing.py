"""Reduction of a profiler trace to the numbers the metrics read.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device planes are ``/device:TPU:<n>``; on each, the line of XLA ops
(``XLA Ops``, else the busiest line) holds one event per operation run.
The traced window is the host span ``bench.traced``, which the harness
opens around the traced slice; host spans named ``bench.*`` say what the
host was doing in each idle gap.

* busy: the union of the op intervals inside the window, per chip, then
  averaged over the chips; idle share = 1 - busy / window.
* kernel time: the summed durations of the ops whose own name (the HLO
  instruction's) contains a kernel's pattern.  The free text of an op's
  statistics is never matched: it names the jitted function around an
  op, so the wrapper's transposes would count as the kernel.
* breakdown: the ops that took most device time, and the longest idle
  gaps with the host span that covers them.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.traced"


def union_length(intervals: np.ndarray) -> float:
    """Total length covered by (start, end) intervals."""
    if len(intervals) == 0:
        return 0.0
    iv = intervals[np.argsort(intervals[:, 0])]
    total = 0.0
    cur_s, cur_e = iv[0]
    for s, e in iv[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    return float(total + cur_e - cur_s)


def merged(intervals: np.ndarray) -> List[Tuple[float, float]]:
    if len(intervals) == 0:
        return []
    iv = intervals[np.argsort(intervals[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s > out[-1][1]:
            out.append([s, e])
        elif e > out[-1][1]:
            out[-1][1] = e
    return [(float(s), float(e)) for s, e in out]


class TraceSummary:
    """Device ops and host spans of the traced window (times in ns)."""

    def __init__(self, window: Tuple[float, float],
                 ops: Sequence[Sequence[Tuple[str, float, float]]],
                 host_spans: Sequence[Tuple[str, float, float]]):
        self.window = window
        self.ops = ops                  # per chip: (name, start, end)
        self.host_spans = host_spans
        w0, w1 = window
        self._clipped = []
        for chip_ops in ops:
            iv = np.array([(max(s, w0), min(e, w1)) for _, s, e in chip_ops
                           if e > w0 and s < w1], float).reshape(-1, 2)
            self._clipped.append(iv)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        per_chip = [union_length(iv) for iv in self._clipped]
        return float(np.mean(per_chip)) * 1e-9 if per_chip else 0.0

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0 or self.busy_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, patterns: Iterable[str]) -> Tuple[float, int]:
        """(seconds, calls) of the ops whose name contains any pattern,
        summed over chips and averaged per chip."""
        pats = tuple(patterns)
        w0, w1 = self.window
        total, calls = 0.0, 0
        for chip_ops in self.ops:
            for name, s, e in chip_ops:
                if e <= w0 or s >= w1:
                    continue
                if any(p in name for p in pats):
                    total += min(e, w1) - max(s, w0)
                    calls += 1
        n = max(len(self.ops), 1)
        return total * 1e-9 / n, calls // n

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        w0, w1 = self.window
        per_op = collections.Counter()
        for name, s, e in (self.ops[0] if self.ops else []):
            if e > w0 and s < w1:
                per_op[name] += min(e, w1) - max(s, w0)
        device_ops = [[k, v * 1e-9] for k, v in per_op.most_common(top)]
        gaps = []
        busy = merged(self._clipped[0]) if self._clipped else []
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                gaps.append((g1 - g0, g0, g1))
        gaps.sort(reverse=True)
        idle = [[self.host_doing((g0 + g1) / 2), d * 1e-9]
                for d, g0, g1 in gaps[:top]]
        return {"device_ops": device_ops, "idle_gaps": idle}

    def host_doing(self, t: float) -> str:
        """The innermost bench.* host span covering time t."""
        best = None
        for name, s, e in self.host_spans:
            if name == WINDOW_SPAN or not (s <= t <= e):
                continue
            if best is None or e - s < best[2] - best[1]:
                best = (name, s, e)
        return best[0] if best else "outside any bench span"


def reduce_profile(pd, chips: int) -> TraceSummary:
    """A TraceSummary from a ``ProfileData``."""
    host_spans = []
    window = None
    device = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            try:
                idx = int(plane.name.split(":")[-1])
            except ValueError:
                continue
            lines = [ln for ln in plane.lines]
            ops_line = next((ln for ln in lines if ln.name == OPS_LINE), None)
            if ops_line is None and lines:
                ops_line = max(lines, key=lambda ln: len(list(ln.events)))
            if ops_line is not None:
                device[idx] = []
                for e in ops_line.events:
                    device[idx].append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith("bench."):
                        span = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        host_spans.append(span)
                        if e.name == WINDOW_SPAN:
                            window = span[1:]
    ops = [device[i] for i in sorted(device)[:chips]]
    if window is None:
        starts = [o[1] for chip in ops for o in chip]
        ends = [o[2] for chip in ops for o in chip]
        window = (min(starts), max(ends)) if starts else (0.0, 0.0)
    return TraceSummary(window, ops, host_spans)


def reduce_dir(trace_dir: str, chips: int) -> TraceSummary:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise RuntimeError(f"no trace under {trace_dir}")
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(files[-1]), chips)


def idle_percent(run) -> Optional[float]:
    """The traced window's device idle share in %, or None untraced."""
    ts = run.trace_summary
    share = ts.idle_share() if ts is not None else None
    return None if share is None else 100 * share

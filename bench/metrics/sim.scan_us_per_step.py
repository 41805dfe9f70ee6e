"""Batched chunk runner: microseconds of device run per scan step, the
median over the window's jobs of timings["run_s"] / (chunks x chunk)."""

from harness.core import quantile


def read(run):
    h = run.host
    return quantile([1e6 * j["timings"]["run_s"]
                     / (j["timings"]["chunks"] * h["chunk"])
                     for j in h["jobs"]], 0.5)

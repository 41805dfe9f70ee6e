"""Engine step: median ms of run(max_steps=1) over the window (host clock)."""

from harness.core import quantile


def read(run):
    return quantile([1e3 * (b - a) for a, b, _ in run.host["steps"]], 0.5)

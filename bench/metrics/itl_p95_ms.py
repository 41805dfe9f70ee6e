"""95th percentile, in ms, of every gap between consecutive generated
tokens of a request in the window (host clock at the end of each step)."""

from harness.core import quantile


def read(run):
    gaps = [1e3 * (b - a) for s in run.host["done"] + run.host["inflight"]
            for a, b in zip(s.token_times, s.token_times[1:])]
    return quantile(gaps, 0.95)

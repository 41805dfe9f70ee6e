"""Median over requests, in s, from when a request was sent to its first
generated token (host clock)."""

from harness.core import quantile


def read(run):
    ttft = [s.token_times[0] - s.sent
            for s in run.host["done"] + run.host["inflight"] if s.token_times]
    return quantile(ttft, 0.5)

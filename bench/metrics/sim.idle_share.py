"""Device idle share, in %, of the traced job: 1 - busy union / window,
averaged over the cell's chips (profiler trace)."""

from harness.tracing import idle_percent as _idle


def read(run):
    return _idle(run)

"""Device idle share, in %, of the traced slice of the window: 1 - busy
union / window (profiler trace)."""

from harness.tracing import idle_percent as _idle


def read(run):
    return _idle(run)

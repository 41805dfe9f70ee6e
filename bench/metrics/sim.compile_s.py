"""Ahead-of-time compile of the chunk runner at warm-up, timings["compile_s"]
(from the persistent cache after a checkout's first run)."""


def read(run):
    return run.host["compile_s"]

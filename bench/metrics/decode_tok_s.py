"""Generated tokens per second: every token generated (not teacher-forced)
in the window, over the window's seconds (host clock)."""


def read(run):
    h = run.host
    n = sum(len(s.token_times) for s in h["done"] + h["inflight"])
    return n / h["window_s"]

"""Set-up: process start to the first timed instant (host clock)."""


def read(run):
    return run.setup_s

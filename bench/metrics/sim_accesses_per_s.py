"""Simulated accesses x mechanisms per second: the work of every bucket job
the window ran (lanes x cores x trace accesses x mechanisms, padding not
counted) over the time from the window's start to the last job's end."""


def read(run):
    h = run.host
    return len(h["jobs"]) * h["work_per_job"] / h["window_s"]

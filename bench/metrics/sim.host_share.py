"""Host part of simulate_batch (trace packing, transfer, dispatch), in %
of a job's wall time: the median over the window's jobs of
(job wall - timings["run_s"]) / job wall."""

from harness.core import quantile


def read(run):
    return quantile([100 * (1 - j["timings"]["run_s"] / (j["end"] - j["start"]))
                     for j in run.host["jobs"]], 0.5)

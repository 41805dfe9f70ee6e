"""Scheduler: the median over requests of the engine steps from the step
after a request was sent to the step that produced its first token."""

from harness.core import quantile


def read(run):
    return quantile([s.first_step - s.sent_step
                     for s in run.host["done"] + run.host["inflight"]
                     if s.first_step > 0], 0.5)

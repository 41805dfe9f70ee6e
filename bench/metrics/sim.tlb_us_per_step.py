"""Batched chunk runner: device microseconds per scan step of the ops in
the program's ``tlb`` scope (profiler trace of one whole job;
``harness/scopes.py``)."""

from harness.scopes import us_per_step


def read(run):
    return us_per_step(run, "tlb")

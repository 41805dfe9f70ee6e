import os
import sys

# NOTE: no xla_force_host_platform_device_count here — smoke tests must see
# the real single CPU device (the dry-run sets 512 itself, in-process).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import pytest

from repro.util.compile_cache import enable_compile_cache

jax.config.update("jax_platform_name", "cpu")

# persist jitted simulator/kernel binaries across test processes: the CI
# fast lane restores this directory so reruns skip XLA compilation
enable_compile_cache()


@pytest.fixture(scope="session")
def smoke():
    """The ``smoke`` SimPreset: tiny footprint, short window, fixed seed.

    Tier-1 tests run the full simulator code path through this preset so
    they stay CI-cheap; full-size runs live behind ``-m slow`` /
    benchmarks.
    """
    from repro.configs.ndp_sim import PRESETS
    return PRESETS["smoke"]


@pytest.fixture(scope="session")
def smoke_trace(smoke):
    """generate_trace pinned to the smoke preset: (workload, cores) ->
    trace dict.  Session-cached so test files share trace generation."""
    from repro.workloads import generate_trace
    cache = {}

    def make(workload: str, cores: int):
        key = (workload, cores)
        if key not in cache:
            cache[key] = generate_trace(workload, cores, preset=smoke)
        return cache[key]

    return make


@pytest.fixture(scope="session")
def smoke_sim(smoke, smoke_trace):
    """simulate() pinned to the smoke preset, session-cached per
    (workload, machine) so the jitted runner compiles once per config."""
    from repro.sim import simulate
    cache = {}

    def run(workload: str, mach, **kw):
        key = (workload, mach, tuple(sorted(kw.items())))
        if key not in cache:
            cache[key] = simulate(mach, smoke_trace(workload,
                                                    mach.num_cores),
                                  chunk=smoke.chunk, **kw)
        return cache[key]

    return run

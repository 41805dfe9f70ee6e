"""Pytest set-up of ``bench/tests``, loaded before ``bench/tests/conftest.py``.

That file's ``make_tiny_bench`` copies the metric entries of the real
``BENCHMARK.json`` into its tiny benchmark and renames the cells each
lists through a fixed map of the cells it mirrors, which raises KeyError
on any other cell.  Its reader of ``BENCHMARK.json`` is given one that
drops the cells the map does not hold; cells with tiny benchmarks of
their own (``test_mla_moe_serve.py``) build them themselves.
"""
import os

#: the cells ``make_tiny_bench`` maps to tiny ones, or drops itself
MIRRORED = {"sim.ndp8", "serve.chat", "sim.cpu4.x4"}


def _mirrored_cells_only(load):
    def read(rel):
        data = load(rel)
        if os.path.basename(rel) == "BENCHMARK.json":
            for m in data["end_to_end"] + data["per_layer"]:
                if "workloads" in m:
                    m["workloads"] = [w for w in m["workloads"]
                                      if w in MIRRORED]
        return data
    return read


def pytest_plugin_registered(plugin, manager):
    if callable(getattr(plugin, "make_tiny_bench", None)):
        plugin._load = _mirrored_cells_only(plugin._load)

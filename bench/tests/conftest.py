"""Tests of the benchmark itself, on the CPU at tiny sizes:

    PYTHONPATH=src python -m pytest bench/tests

A tiny benchmark is laid out in a temporary directory the way ``bench/``
is, with its own configurations and mixes, and the real systems and
metric readers linked in; runs skip the look for a chip.
"""
import json
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

TINY_SIM = {
    "name": "tiny-ndp", "system": "sim",
    "machine": None,                      # filled from ndp-8c below
    "mechanisms": None,
}
TINY_LM = {
    "name": "tiny-lm", "system": "lm_serve", "hidden_act": "silu",
    "bias": False, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 257, "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0, "tie_word_embeddings": False,
    "torch_dtype": "float32",
    "serving": {"max_batch": 4, "max_len": 96, "page_size": 8,
                "block_table": "paged_flat", "decoding": "greedy"},
}
TINY_CHAT = {"name": "t.chat", "generator": "chat", "clients": 4,
             "prompt": {"median": 7, "sigma": 0.4},
             "output": {"median": 3, "sigma": 0.4},
             "quantiles": 4, "pair_stride": 3, "correctness_sample": 12}


def _load(rel):
    with open(os.path.join(BENCH, rel)) as f:
        return json.load(f)


def tiny_sim_config(cores=2):
    ndp = _load("configs/ndp-8c.json")
    # the simulator's calibration at this core count
    frac = {1: 0.16, 2: 0.27, 4: 0.49, 8: 0.93}.get(
        cores, min(0.93, 0.05 + 0.11 * cores))
    m = dict(ndp["machine"], num_cores=cores, fragmented_4k_share=frac,
             hugepage_stall_cycles=55.0 + 7.0 * (cores - 1),
             ech_rehash_cycles=5.0 * max(cores - 2, 0) ** 2)
    return dict(TINY_SIM, machine=m, mechanisms=ndp["mechanisms"],
                accesses_per_core=1024)


def tiny_sim_mix(chunk=512, n=3):
    mix = _load("traffic/sim.table2.json")
    wl = dict(list(mix["workloads"].items())[:n])
    return dict(mix, name="t.sim", workloads=wl, chunk=chunk)


@pytest.fixture
def tiny_bench(tmp_path):
    """A benchmark directory with tiny cells ``t.sim`` and ``t.serve``."""
    return make_tiny_bench(tmp_path)


def make_tiny_bench(tmp_path):
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    os.symlink(os.path.join(BENCH, "systems"), bench / "systems")
    for f in os.listdir(os.path.join(BENCH, "metrics")):
        os.symlink(os.path.join(BENCH, "metrics", f), bench / "metrics" / f)
    files = {"configs/tiny-ndp.json": tiny_sim_config(),
             "configs/tiny-lm.json": TINY_LM,
             "traffic/t.sim.json": tiny_sim_mix(),
             "traffic/t.chat.json": TINY_CHAT}
    for rel, data in files.items():
        (bench / rel).write_text(json.dumps(data))
    real = _load(os.path.join(ROOT, "BENCHMARK.json"))
    benchmark = {
        "configs": [{"name": "tiny-ndp", "file": "bench/configs/tiny-ndp.json"},
                    {"name": "tiny-lm", "file": "bench/configs/tiny-lm.json"}],
        "workloads": [
            {"name": "t.sim", "config": "tiny-ndp", "traffic": "t.sim",
             "chips": 1},
            {"name": "t.serve", "config": "tiny-lm", "traffic": "t.chat",
             "chips": 1}],
        "end_to_end": [dict(m, workloads=[{"sim.ndp8": "t.sim",
                                           "serve.chat": "t.serve"}[w]
                                          for w in m["workloads"]
                                          if w != "sim.cpu4.x4"])
                       if "workloads" in m else m
                       for m in real["end_to_end"]],
        "per_layer": [dict(m, workloads=[{"sim.ndp8": "t.sim",
                                          "serve.chat": "t.serve"}[w]
                                         for w in m["workloads"]
                                         if w != "sim.cpu4.x4"])
                      for m in real["per_layer"]],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(benchmark))
    return bench


def run_tiny(bench, cell, seed=1234567890123, seconds=1.0, trace=False):
    """A whole run of a tiny cell on the CPU; returns the result line."""
    import jax
    from harness import core
    c = core.Cell(cell, bench_dir=str(bench))
    return core.run_cell(c, seed, seconds, trace, time.time(),
                         jax.devices("cpu")[:1])

"""chip_smoke.py without a chip: it refuses to run on the CPU, and its
phases run end to end at a tiny size on the CPU with the kernels in
Pallas interpret mode (the test substitutes the device check, the sizes
and the compiled-kernel mark)."""
import dataclasses
import importlib.util
import json
import os
import subprocess
import sys

import jax

from repro import config as C
from repro.configs.ndp_sim import PRESETS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_chip():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert "found no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_phases_end_to_end_tiny_on_cpu(monkeypatch, capsys):
    cs = _load_chip_smoke()
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    monkeypatch.setenv("SIM_TRACE_CACHE", "0")     # restored afterwards
    monkeypatch.setattr(cs, "require_tpu", lambda chips: jax.devices())
    monkeypatch.setattr(cs, "sim_setup", lambda: (
        PRESETS["smoke"], [("ndp", 1), ("ndp", 8)]))
    cfg = C.smoke_variant(C.get_arch("internlm2-1.8b"))
    monkeypatch.setattr(cs, "serve_setup", lambda: (
        dataclasses.replace(cfg, dtype="bfloat16"),
        dict(max_batch=4, max_len=64, page_size=8, requests=6,
             prompt_len=(4, 10), new_tokens=4)))
    monkeypatch.setattr(cs, "KERNEL_CHECK_STEP", 3)
    # interpret mode lowers the kernel to plain HLO under its jit's name
    monkeypatch.setattr(cs, "KERNEL_MARKER", "paged_attention_pallas")

    assert cs.main([]) == 0
    out = capsys.readouterr().out
    assert "ndp-8c on cpu" in out
    assert "identical tokens" in out
    last = json.loads(out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}

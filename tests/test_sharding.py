"""Sharding rules + an 8-device mini dry-run (subprocess: device count must
be set before jax init, and the main test process keeps 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import _rule_for, valid_spec


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 4, "model": 2}


def test_valid_spec_drops_nondivisible():
    m = FakeMesh()
    assert valid_spec(P("model"), (3,), m) == P(None)
    assert valid_spec(P("model"), (4,), m) == P("model")
    assert valid_spec(P(("data", "model")), (8,), m) == P(("data", "model"))
    assert valid_spec(P(("data", "model")), (4,), m) == P("data")
    assert valid_spec(P("data", "model"), (8, 7), m) == P("data", None)


def test_param_rules():
    assert _rule_for(("stack", "mixer", "wq"), 2, True) == P("data", "model")
    assert _rule_for(("stack", "mixer", "wo"), 2, False) == P("model", None)
    assert _rule_for(("ffn", "w_up"), 3, True) == P("model", "data", None)
    assert _rule_for(("norm1", "scale"), 1, True) == P()


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MINI_DRYRUN = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    from repro import config as C
    from repro.launch import dryrun as D
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((4, 2), ("data", "model"))
    out = {}
    import dataclasses
    shape = dataclasses.replace(C.SHAPES["train_4k"], global_batch=8,
                                seq_len=256)
    D.MICROBATCH["train_4k"] = 2
    for arch in %s:
        cfg = C.smoke_variant(C.get_arch(arch))
        cfg = dataclasses.replace(cfg, name=cfg.name)
        lowered = D.lower_train(cfg, shape, mesh)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        out[arch] = {"temp": mem.temp_size_in_bytes,
                     "flops": ca.get("flops", 0)}
    dshape = dataclasses.replace(C.SHAPES["decode_32k"], global_batch=8,
                                 seq_len=256)
    cfg = C.smoke_variant(C.get_arch("internlm2-1.8b"))
    compiled = D.lower_serve(cfg, dshape, mesh).compile()
    out["serve"] = {"temp": compiled.memory_analysis().temp_size_in_bytes}
    print("RESULT:" + json.dumps(out))
""")


@pytest.mark.slow
def test_mini_dryrun_8_devices():
    """Lower+compile smoke train/serve steps on a real 4x2 mesh."""
    archs = '["internlm2-1.8b", "granite-moe-1b-a400m"]'
    proc = subprocess.run(
        [sys.executable, "-c", MINI_DRYRUN % archs],
        capture_output=True, text=True, timeout=560,
        env=dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
                 JAX_PLATFORMS="cpu"),
        cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    out = json.loads(line[0][len("RESULT:"):])
    assert out["internlm2-1.8b"]["flops"] > 0
    assert out["serve"]["temp"] > 0

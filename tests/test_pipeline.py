"""Pipeline-parallel stage runner: matches sequential execution
(subprocess: needs >1 host device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.parallel.pipeline import bubble_fraction

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bubble_fraction():
    assert bubble_fraction(4, 12) == 3 / 15
    assert bubble_fraction(1, 8) == 0.0


PIPE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import auto_mesh
    from repro.parallel.pipeline import pipeline_run

    mesh = auto_mesh((4,), ("stage",))
    S, M, mb, seq, d = 4, 6, 2, 8, 16
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (S, d, d)) * 0.3

    def stage_fn(w_s, x):
        return jnp.tanh(x @ w_s)

    x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, seq, d))
    got = pipeline_run(mesh, "stage", stage_fn, w, x)

    want = x
    for s in range(S):
        want = jnp.tanh(want @ w[s])
    err = float(jnp.abs(got - want).max())
    print("RESULT:" + str(err))
""")


@pytest.mark.slow
def test_pipeline_matches_sequential():
    proc = subprocess.run(
        [sys.executable, "-c", PIPE], capture_output=True, text=True,
        timeout=300,
        env=dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
                 JAX_PLATFORMS="cpu"),
        cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    assert float(line[0][len("RESULT:"):]) < 1e-5

"""The name by which ``paged_attn_roofline`` finds the kernel in a trace
is the name the compiler gives it in the decode step: compiled here for a
described TPU v5e (no chip), at the configuration's widths with two
layers.  The step's other custom calls and the wrapper's pool transposes
must not carry it."""
import json
import os
import re

import pytest

from conftest import BENCH


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


def test_decode_step_names_the_kernel(one_chip, monkeypatch):
    import jax
    import jax.numpy as jnp
    import repro.kernels.ops as ops
    from harness import lm_weights
    from repro.models.model_zoo import decode_step, init_decode_state
    from systems import lm_serve
    # the program picks its Pallas kernel by the first device's platform,
    # which is the CPU here
    monkeypatch.setattr(ops, "_impl", lambda override: "pallas")
    with open(os.path.join(BENCH, "configs", "internlm2-1_8b.json")) as f:
        cfg = json.load(f)
    cfg["num_hidden_layers"] = 2
    dep = cfg["serving"]
    arch = lm_serve.arch_config(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    w = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16)
         for k, s in lm_weights.shapes(cfg).items()}
    params = on_chip(jax.eval_shape(lm_weights.program_tree, w))
    state = on_chip(jax.eval_shape(lambda: init_decode_state(
        arch, dep["max_batch"], dep["max_len"], dep["block_table"],
        dep["page_size"])))
    tokens = jax.ShapeDtypeStruct((dep["max_batch"],), jnp.int32,
                                  sharding=one_chip)
    hlo = decode_step.lower(params, arch, state, tokens,
                            dep["block_table"]).compile().as_text()
    from harness.core import load_module
    reader = load_module(os.path.join(BENCH, "metrics",
                                      "paged_attn_roofline.py"),
                         "bench_metric_paged_attn_roofline")
    # HLO instructions are the ops of a TPU trace, by the same names
    names = {line: m.group(2) for line in hlo.splitlines()
             if (m := re.match(r"\s*(ROOT )?%(\S+) = ", line))}
    kernel = [line for line, name in names.items()
              if any(p in name for p in reader.KERNEL)]
    assert len(kernel) == 1, kernel
    assert 'custom_call_target="tpu_custom_call"' in kernel[0]
    others = [line for line in hlo.splitlines()
              if "custom-call(" in line and line not in kernel]
    assert others, "the step has other custom calls, which must not match"

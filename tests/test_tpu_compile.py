"""Compiles for a described TPU v5e chip, with no chip attached: the
Pallas kernels of the serving path at internlm2-1.8b widths and the
simulator's batched chunk runner for two figure buckets.  The TPU
compiler refuses here what the chip would refuse (tiling, VMEM, device
memory) at no chip time.  Nothing runs, so these say nothing about
results or speed.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import config as C
from repro.configs.ndp_sim import PRESETS, WORKLOADS, cpu_machine, ndp_machine

KERNEL_MARKER = "tpu_custom_call"


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # else it logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                     # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep it out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_paged_attention_internlm2_widths(one_chip):
    """The one-chip serving deployment: 8 sequences of up to 2048 tokens
    in 16-token bf16 pages (128 pages each, 1032 in the pool)."""
    from repro.kernels.paged_attention import paged_attention_pallas
    cfg = C.get_arch("internlm2-1.8b")
    b, maxp, page = 8, 128, 16
    q = _spec(one_chip, (b, 1, cfg.num_heads, cfg.head_dim), jnp.bfloat16)
    kv = _spec(one_chip, (b * maxp + 8, page, cfg.num_kv_heads,
                          cfg.head_dim), jnp.bfloat16)
    table = _spec(one_chip, (b, maxp), jnp.int32)
    lens = _spec(one_chip, (b,), jnp.int32)
    text = paged_attention_pallas.lower(q, kv, kv, table, lens).compile(
        ).as_text()
    assert KERNEL_MARKER in text
    # the kernel's own name, not its jitted wrapper's, names the custom
    # call: the op a device trace shows
    call = re.search(r"%(\S+) = \S+ custom-call\(.*tpu_custom_call", text)
    assert call and call.group(1).startswith("paged_attention_pallas_kernel")


def test_flash_attention_s2048(one_chip):
    from repro.kernels.flash_attention import flash_attention_pallas
    cfg = C.get_arch("internlm2-1.8b")
    s = 2048
    q = _spec(one_chip, (1, s, cfg.num_heads, cfg.head_dim), jnp.bfloat16)
    kv = _spec(one_chip, (1, s, cfg.num_kv_heads, cfg.head_dim),
               jnp.bfloat16)
    compiled = flash_attention_pallas.lower(q, kv, kv).compile()
    assert KERNEL_MARKER in compiled.as_text()


@pytest.mark.parametrize("machine,cores", [("ndp", 8), ("cpu", 4)])
def test_sim_chunk_runner(one_chip, machine, cores):
    """The batched chunk runner of one figure bucket: the 11 Table-II
    workloads on the lane axis, the paper-figure preset's chunk."""
    from repro.sim import simulator as S
    mach = ndp_machine(cores) if machine == "ndp" else cpu_machine(cores)
    lanes, chunk = len(WORKLOADS), PRESETS["full"].chunk
    on = lambda tree: jax.tree.map(                          # noqa: E731
        lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    state = on(jax.eval_shape(
        lambda: S.init_state(mach, len(S.MECHS), batch=lanes)))
    tbc = (chunk, lanes, cores)
    xs = (_spec(one_chip, tbc, jnp.int32), _spec(one_chip, tbc, jnp.int32),
          _spec(one_chip, tbc, jnp.float32), _spec(one_chip, tbc, jnp.bool_),
          _spec(one_chip, (chunk, lanes), jnp.bool_))
    mt = {k: _spec(one_chip, (lanes,) + v.shape, v.dtype)
          for k, v in S._mech_arrays(S.MECHS).items()}
    dp = {k: _spec(one_chip, (lanes,), np.float32)
          for k in S._data_params(mach)}
    runner = S._chunk_runner(S.machine_shape(mach), S._walk_fns(S.MECHS),
                             chunk, batch=True)
    mem = runner.lower(state, xs, mt, dp).compile().memory_analysis()
    assert mem.argument_size_in_bytes > 0

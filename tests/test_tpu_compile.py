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
    in 16-token bf16 pages (128 pages each, 1032 in the pool), one
    layer's pools or the pools of every layer stacked and read at a
    layer index."""
    from repro.kernels.paged_attention import paged_attention_pallas
    cfg = C.get_arch("internlm2-1.8b")
    b, maxp, page = 8, 128, 16
    q = _spec(one_chip, (b, 1, cfg.num_heads, cfg.head_dim), jnp.bfloat16)
    shape = (b * maxp + 8, page, cfg.num_kv_heads, cfg.head_dim)
    table = _spec(one_chip, (b, maxp), jnp.int32)
    lens = _spec(one_chip, (b,), jnp.int32)
    layer = _spec(one_chip, (), jnp.int32)
    # no instruction outside the kernel makes a pool-sized array (one
    # layer's or the stack's), in the pools' layout or transposed
    pool = re.compile(r"bf16\[(\d+,)?(%d,%d,%d,%d|%d,%d,%d,%d)\]" % (
        shape + (shape[2], shape[0], page, cfg.head_dim)))
    for kv_shape in (shape, (cfg.num_layers,) + shape):
        kv = _spec(one_chip, kv_shape, jnp.bfloat16)
        text = paged_attention_pallas.lower(q, kv, kv, table, lens,
                                            layer).compile().as_text()
        assert KERNEL_MARKER in text
        # the kernel's own name, not its jitted wrapper's, names the
        # custom call: the op a device trace shows
        call = re.search(r"%(\S+) = \S+ custom-call\(.*tpu_custom_call",
                         text)
        assert call and call.group(1).startswith(
            "paged_attention_pallas_kernel")
        made = [line for line in text.splitlines()
                if (m := re.match(r"\s*(ROOT )?%\S+ = (\S+) ([\w-]+)\(",
                                  line))
                and pool.match(m.group(2)) and m.group(3) != "parameter"]
        assert not made, made


def test_decode_step_reads_the_kernel_through_a_bitcast(one_chip,
                                                        monkeypatch):
    """In the served decode step (internlm2-1.8b widths, two layers, the
    one-chip deployment), the kernel's output feeds the step through a
    bitcast: no op that runs on the device names the kernel among its
    operands, so a device trace holds one op per kernel call that
    carries its name."""
    import dataclasses
    import repro.kernels.ops as ops
    from repro.core import block_table as BT
    from repro.models import decode_step, init_decode_state, init_params
    monkeypatch.setattr(ops, "_impl", lambda override: "pallas")
    cfg = dataclasses.replace(C.get_arch("internlm2-1.8b"), num_layers=2)
    on_chip = lambda tree: jax.tree.map(                     # noqa: E731
        lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    state = on_chip(jax.eval_shape(lambda: init_decode_state(
        cfg, 8, 2048, BT.FLAT, 16, num_pages=8 * 128 + 8)))
    tokens = _spec(one_chip, (8,), jnp.int32)
    text = decode_step.lower(params, cfg, state, tokens, BT.FLAT).compile(
        ).as_text()
    kernel = re.findall(r"%(paged_attention_pallas_kernel[\w.]*) = ", text)
    assert len(kernel) == 1, kernel
    uses = [line for line in text.splitlines()
            if re.search(r"%" + re.escape(kernel[0]) + r"[,)]", line)]
    assert uses and all(" bitcast(" in line for line in uses), uses


def test_decode_step_appends_pools_in_place(one_chip, monkeypatch):
    """The served decode step (internlm2-1.8b widths, two scanned layers,
    the one-chip deployment) is handed the state's buffers and appends
    each layer's new K/V into the stacked pools in place: the pools come
    back in the buffers they came in, no op copies or writes back a pool
    or one layer of it, and no op makes one layer's pools: the kernel
    reads them out of the stack at the layer's index."""
    import dataclasses
    import repro.kernels.ops as ops
    from repro.core import block_table as BT
    from repro.models import decode_step, init_decode_state, init_params
    monkeypatch.setattr(ops, "_impl", lambda override: "pallas")
    cfg = dataclasses.replace(C.get_arch("internlm2-1.8b"), num_layers=2)
    on_chip = lambda tree: jax.tree.map(                     # noqa: E731
        lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    state = on_chip(jax.eval_shape(lambda: init_decode_state(
        cfg, 8, 2048, BT.FLAT, 16, num_pages=8 * 128 + 8)))
    kp = state["stack"]["scan"]["block_0"]["kp"]
    assert kp.shape == (2, 8 * 128 + 8, 16, 8, 128)
    pools = 2 * kp.size * kp.dtype.itemsize
    tokens = _spec(one_chip, (8,), jnp.int32)
    compiled = decode_step.lower(params, cfg, state, tokens,
                                 BT.FLAT).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < pools // 4       # under one layer's
    pool = re.compile(r"bf16\[(2,)?1032,16,8,128\]")
    rewritten = [
        line for line in compiled.as_text().splitlines()
        if (m := re.match(r"\s*(ROOT )?%\S+ = (\S+) ([\w-]+)\(", line))
        and pool.match(m.group(2))
        and m.group(3) in ("copy", "dynamic-update-slice")]
    assert not rewritten, rewritten
    layer = re.compile(r"bf16\[(1,)?1032,16,8,128\]")
    sliced = [
        line for line in compiled.as_text().splitlines()
        if (m := re.match(r"\s*(ROOT )?%\S+ = (\S+) ([\w-]+)\(", line))
        and layer.match(m.group(2))
        and m.group(3) not in ("parameter", "get-tuple-element", "bitcast")]
    assert not sliced, sliced


def test_deepseek_v2_decode_step_names_the_latent_kernel(one_chip,
                                                        monkeypatch):
    """DeepSeek-V2's decode step at its published widths (the dense layer
    and one expert layer holding 8 of 160 experts; 64 slots of 4096
    tokens in 16-token latent pages): one latent kernel per layer, named
    ``paged_latent_attention_kernel`` (what ``mla_paged_roofline``
    matches in an op's own name), which never carries the GQA kernel's
    name; expert and attention ops carry their named scopes."""
    import dataclasses
    import repro.kernels.ops as ops
    from repro.core import block_table as BT
    from repro.models import decode_step, init_decode_state, init_params
    monkeypatch.setattr(ops, "_impl", lambda override: "pallas")
    full = C.get_arch("deepseek-v2-236b")
    cfg = dataclasses.replace(full, num_layers=2, moe=dataclasses.replace(
        full.moe, experts_held=8))
    on_chip = lambda tree: jax.tree.map(                     # noqa: E731
        lambda a: _spec(one_chip, a.shape, a.dtype), tree)
    params = on_chip(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))))
    state = on_chip(jax.eval_shape(lambda: init_decode_state(
        cfg, 64, 4096, BT.FLAT, 16, num_pages=64 * 256 + 8)))
    assert state["stack"]["prefix"][0]["lat"].shape == (64 * 256 + 8, 16,
                                                          640)
    tokens = _spec(one_chip, (64,), jnp.int32)
    text = decode_step.lower(params, cfg, state, tokens, BT.FLAT).compile(
        ).as_text()
    names = re.findall(r"^\s*(?:ROOT )?%(\S+) = .*custom-call\(.*"
                       r"tpu_custom_call", text, re.M)
    kernel = [n for n in names if "paged_latent_attention_kernel" in n]
    assert len(kernel) == 2, names
    assert not any("paged_attention_pallas" in n for n in names)
    assert 'op_name="jit(decode_step)/while/body/closed_call/moe/' in text
    assert "/mla/" in text


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
        lambda: S.init_state(mach, len(S.MECHS), lanes)))
    tbc = (chunk, lanes, cores)
    xs = (_spec(one_chip, tbc, jnp.int32), _spec(one_chip, tbc, jnp.int32),
          _spec(one_chip, tbc, jnp.float32), _spec(one_chip, tbc, jnp.bool_),
          _spec(one_chip, (chunk, lanes), jnp.bool_))
    mt = {k: _spec(one_chip, (lanes,) + v.shape, v.dtype)
          for k, v in S._mech_arrays(S.MECHS).items()}
    dp = {k: _spec(one_chip, (lanes,), np.float32)
          for k in S._data_params(mach)}
    runner = S._chunk_runner(S.machine_shape(mach), S._walk_fns(S.MECHS),
                             chunk)
    mem = runner.lower(state, xs, mt, dp).compile().memory_analysis()
    assert mem.argument_size_in_bytes > 0

"""Pallas TPU paged-attention decode kernel — NDPage's mechanisms on TPU.

The paper's two ideas, re-expressed in the TPU memory hierarchy:

  1. *Flattened table* — the block table is a single-level (B, max_pages)
     int32 map.  The k/v BlockSpec ``index_map`` reads it directly to pick
     which physical page to DMA next: ONE metadata indirection per page,
     not a directory walk.

  2. *Metadata bypass* — the table and sequence lengths are
     **scalar-prefetch operands** (``pltpu.PrefetchScalarGridSpec``): they
     are staged into SMEM for the scalar core ahead of the grid and never
     travel through the HBM->VMEM vector pipeline, so translation metadata
     cannot displace KV tiles from VMEM — the exact analogue of "PTEs
     bypass the L1 and stop polluting the data cache".

Layouts (wrapper-normalized):
  q: (B, KH, G, D)   one decode token per sequence, grouped query heads
  k/v pools: (KH, N, page, D)
  block_table: (B, MAXP) int32 (-1 = unmapped)   [scalar prefetch]
  lengths: (B,) int32 attendable tokens           [scalar prefetch]
Grid: (B, KH, MAXP); online softmax accumulates in VMEM scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(table_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page: int, window: int,
            scale: float):
    b = pl.program_id(0)
    p = pl.program_id(2)
    maxp = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]                                   # (G, D)
    k = k_ref[0, 0]                                   # (page, D)
    v = v_ref[0, 0]                                   # (page, D)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (G, page)

    length = lens_ref[b]
    token_pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = token_pos < length
    if window > 0:
        valid &= token_pos >= length - window
    valid &= table_ref[b, p] >= 0
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                               # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_new), 0.0)
    pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # (G, page)
    l_new = l_ref[...] * alpha + pexp.sum(axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)           # (G, D)
    acc_new = acc_ref[...] * alpha + pv

    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(p == maxp - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, block_table: jnp.ndarray,
                           lengths: jnp.ndarray, *, window: int = 0,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B, 1, H, D); k/v_pages: (N, page, K, D); block_table: (B, MAXP).

    Returns (B, 1, H, D).  ``interpret=True`` runs the kernel body on CPU
    for validation (this container); on TPU it compiles to Mosaic.
    """
    b, s1, h, d = q.shape
    n, page, kh, _ = k_pages.shape
    g = h // kh
    maxp = block_table.shape[1]
    scale = 1.0 / math.sqrt(d)

    qk = q.reshape(b, kh, g, d)
    kp = k_pages.transpose(2, 0, 1, 3)                # (KH, N, page, D)
    vp = v_pages.transpose(2, 0, 1, 3)

    def q_map(bi, ki, pi, tab, lens):
        return (bi, ki, 0, 0)

    def kv_map(bi, ki, pi, tab, lens):
        return (ki, jnp.maximum(tab[bi, pi], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kh, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), q_map),
            pl.BlockSpec((1, 1, page, d), kv_map),
            pl.BlockSpec((1, 1, page, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page=page, window=window, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), q.dtype),
        interpret=interpret,
        # the kernel's own name in compiled programs and device traces,
        # whatever jitted function calls it
        name="paged_attention_pallas_kernel",
    )(block_table, lengths, qk, kp, vp)
    return out.reshape(b, 1, h, d)

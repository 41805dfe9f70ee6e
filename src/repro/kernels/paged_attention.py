"""Pallas TPU paged-attention decode kernel — NDPage's mechanisms on TPU.

The paper's two ideas, re-expressed in the TPU memory hierarchy:

  1. *Flattened table* — the block table is a single-level (B, max_pages)
     int32 map.  The kernel reads it directly to pick which physical page
     to DMA next: ONE metadata indirection per page, not a directory walk.

  2. *Metadata bypass* — the table and sequence lengths are
     **scalar-prefetch operands** (``pltpu.PrefetchScalarGridSpec``): they
     are staged into SMEM for the scalar core ahead of the kernel and
     never travel through the HBM->VMEM vector pipeline, so translation
     metadata cannot displace KV tiles from VMEM — the exact analogue of
     "PTEs bypass the L1 and stop polluting the data cache".

Layouts:
  q: (B, H, D)   one decode token per sequence, H = KH * G query heads
  out: (B, KH, G, D)
  k/v pools: (N, page, KH, D), or (L, N, page, KH, D) stacked over
        layers, read in place from HBM
  block_table: (B, MAXP) int32 (-1 = unmapped)   [scalar prefetch]
  lengths: (B,) int32 attendable tokens           [scalar prefetch]
  layer: (1,) int32, the layer of a stacked pool  [scalar prefetch]

Work scales with the live pages, not with MAXP.  The scalar core lists
every live block — ``ppb`` pages of one sequence, ppb derived from the
shapes — and one loop walks that list.  A block's mapped pages within
the live range are DMA'd whole ((page, KH, D), contiguous in HBM) into
a double buffer while the previous block is computed; unmapped and dead
pages are never fetched.  A block is scored as (ppb*page*KH, D) rows,
all query heads at once, with the pairs whose KV head is not the query
head's masked; the online softmax accumulates in f32 scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: K/V rows (token x KV head pairs) a block aims at; a block's pages
#: fit the bits of one int32 mask
BLOCK_ROWS = 1024
MAX_BLOCK_PAGES = 16


def pages_tile(k_pages) -> bool:
    """Whether the compiled kernel can DMA a page of this pool: Mosaic
    slices a pool by pages only where a page's (KH, D) lie on whole
    (8, 128) tiles.  Interpret mode takes any widths."""
    kh, d = k_pages.shape[-2:]
    return kh % 8 == 0 and d % 128 == 0


def block_pages(page: int, kh: int, maxp: int) -> int:
    """Pages per block: about :data:`BLOCK_ROWS` rows, a divisor of maxp."""
    ppb = max(1, min(maxp, MAX_BLOCK_PAGES, BLOCK_ROWS // (page * kh)))
    while maxp % ppb:
        ppb -= 1
    return ppb


def _kernel(table_ref, lens_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, work_b, work_i, m_ref, l_ref, acc_ref,
            *, page: int, ppb: int, kh: int, g: int, window: int,
            scale: float):
    nseq = lens_ref.shape[0]
    bk = ppb * page                       # tokens a block
    rows = bk * kh                        # K/V rows a block

    def live(b, p):
        """Whether page p of sequence b is mapped and holds live tokens."""
        length = lens_ref[b]
        ok = (table_ref[b, p] >= 0) & (p * page < length)
        if window > 0:
            ok &= (p + 1) * page > length - window
        return ok

    # the work list: (sequence, block) of every block with live tokens
    def list_seq(b, t):
        length = lens_ref[b]
        lo = jnp.maximum(length - window, 0) // bk if window > 0 else 0

        def put(i, t):
            work_b[t] = b
            work_i[t] = i
            return t + 1
        return jax.lax.fori_loop(lo, (length + bk - 1) // bk, put, t)
    total = jax.lax.fori_loop(0, nseq, list_seq, 0)

    def copies(t, slot, wait: bool):
        b, blk = work_b[t], work_i[t]
        for i in range(ppb):
            p = blk * ppb + i

            @pl.when(live(b, p))
            def _():
                pid = table_ref[b, p]
                for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                    src = (hbm.at[layer_ref[0], pid] if len(hbm.shape) == 5
                           else hbm.at[pid])
                    cp = pltpu.make_async_copy(src, buf.at[slot, i],
                                               sems.at[slot])
                    cp.wait() if wait else cp.start()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    # pages never fetched into a slot must not feed NaN into p @ v
    v_buf[...] = jnp.zeros_like(v_buf)

    @pl.when(total > 0)
    def _():
        copies(0, 0, wait=False)

    col = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    col_tok = col // kh                   # token of a row within its block
    col_page = col_tok // page
    same_head = (jax.lax.broadcasted_iota(jnp.int32, (kh * g, 1), 0) // g
                 == col % kh)             # (H, rows)

    def step(t, carry):
        slot = t % 2

        @pl.when(t + 1 < total)
        def _():
            copies(t + 1, 1 - slot, wait=False)

        copies(t, slot, wait=True)
        b, blk = work_b[t], work_i[t]
        length = lens_ref[b]
        dead = 0                          # bit i: page i not fetched
        for i in range(ppb):
            dead |= jnp.where(live(b, blk * ppb + i), 0, 1 << i)

        pos = blk * bk + col_tok
        valid = (pos < length) & (((dead >> col_page) & 1) == 0)
        if window > 0:
            valid &= pos >= length - window
        valid &= same_head

        k = k_buf[slot].reshape(rows, -1)
        v = v_buf[slot].reshape(rows, -1)
        s = jax.lax.dot_general(
            q_ref[b], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (H, rows)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[b]                                  # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_new), 0.0)
        pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[b] = l_ref[b] * alpha + pexp.sum(axis=-1, keepdims=True)
        acc_ref[b] = acc_ref[b] * alpha + jax.lax.dot_general(
            pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (H, D)
        m_ref[b] = m_new
        return carry

    jax.lax.fori_loop(0, total, step, 0)
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)   # (B, H, D)
    for k in range(kh):
        o_ref[:, k] = out[:, k * g:(k + 1) * g].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, block_table: jnp.ndarray,
                           lengths: jnp.ndarray, layer=0, *,
                           window: int = 0,
                           interpret: bool = False) -> jnp.ndarray:
    """q: (B, 1, H, D); k/v_pages: (N, page, K, D) or (L, N, page, K, D)
    with ``layer`` the one to read; block_table: (B, MAXP).

    Returns (B, 1, H, D).  ``interpret=True`` runs the kernel body on CPU
    for validation (this container); on TPU it compiles to Mosaic.
    """
    if not (interpret or pages_tile(k_pages)):
        raise ValueError(f"pages of shape {k_pages.shape[-3:]} do not lie "
                         "on whole (8, 128) tiles: use the jnp reference")
    b, _, h, d = q.shape
    page, kh, _ = k_pages.shape[-3:]
    g = h // kh
    maxp = block_table.shape[1]
    ppb = block_pages(page, kh, maxp)
    whole = lambda *_: (0,) * 3                      # noqa: E731
    # the output stays grouped, (B, KH, G, D): a decode step then reads it
    # through a bitcast, so no op that runs on the device takes the
    # kernel as an operand and carries its name in the op's text
    out_whole = lambda *_: (0,) * 4                  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((b, h, d), whole),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((b, kh, g, d), out_whole),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, kh, d), k_pages.dtype),
            pltpu.VMEM((2, ppb, page, kh, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((b * (maxp // ppb),), jnp.int32),
            pltpu.SMEM((b * (maxp // ppb),), jnp.int32),
            pltpu.VMEM((b, h, 1), jnp.float32),
            pltpu.VMEM((b, h, 1), jnp.float32),
            pltpu.VMEM((b, h, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page=page, ppb=ppb, kh=kh, g=g,
                          window=window, scale=1.0 / math.sqrt(d)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the kernel's own name in compiled programs and device traces,
        # whatever jitted function calls it
        name="paged_attention_pallas_kernel",
    )(block_table, lengths, jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      q.reshape(b, h, d), k_pages, v_pages)
    return out.reshape(b, 1, h, d)

"""Pallas TPU paged latent-attention decode kernel (DeepSeek-V2's MLA).

Multi-head latent attention caches one row a token, shared by every
query head: the compressed ``c_kv`` (``kv_lora`` wide) followed by the
rotary key part.  With ``W_uk`` absorbed into the query, each head
scores against that row and reads its value from the row's first
``kv_lora`` lanes, so a block of T latent rows costs (H x R)·(R x T) and
(H x T)·(T x kv_lora) on the MXU for T rows of bytes: at 128 heads about
240 FLOP a byte, the v5e's ridge.

Layouts:
  q: (B, H, R)   absorbed queries, [q_nope·W_uk | q_rope | zero padding]
  out: (B, H, kv_lora)   softmax-weighted latent rows, before W_uv
  pool: (N, page, R), or (L, N, page, R) stacked over layers, read in
        place from HBM
  block_table: (B, MAXP) int32 (-1 = unmapped)   [scalar prefetch]
  lengths: (B,) int32 attendable tokens           [scalar prefetch]
  layer: (1,) int32, the layer of a stacked pool  [scalar prefetch]

The translation is the paged-attention kernel's: the flat table and the
lengths sit in SMEM for the scalar core, which walks each sequence's live
blocks (``ppb`` pages) and DMAs every mapped live page whole into a double
buffer while the previous block is computed; unmapped and dead pages are
never fetched.  The grid runs over sequences, so a sequence's queries,
output and f32 accumulators are all that VMEM holds of the batch; the
last block of one sequence prefetches the first of the next, so the
double buffer stays full across grid steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: latent rows (tokens) a block aims at; a block's pages fit the bits of
#: one int32 mask
BLOCK_ROWS = 256
MAX_BLOCK_PAGES = 16


def block_pages(page: int, maxp: int) -> int:
    """Pages per block: about :data:`BLOCK_ROWS` rows, a divisor of maxp."""
    ppb = max(1, min(maxp, MAX_BLOCK_PAGES, BLOCK_ROWS // page))
    while maxp % ppb:
        ppb -= 1
    return ppb


def _kernel(table_ref, lens_ref, layer_ref, q_ref, pool_hbm, o_ref,
            buf, sems, done_ref, m_ref, l_ref, acc_ref,
            *, page: int, ppb: int, kv_lora: int, scale: float):
    b = pl.program_id(0)
    nseq = pl.num_programs(0)
    bk = ppb * page                       # tokens a block

    def blocks(s):
        return (lens_ref[s] + bk - 1) // bk

    def live(s, p):
        """Whether page p of sequence s is mapped and holds live tokens."""
        return (table_ref[s, p] >= 0) & (p * page < lens_ref[s])

    def copies(s, blk, slot, wait: bool):
        for i in range(ppb):
            p = blk * ppb + i

            @pl.when(live(s, p))
            def _():
                cp = pltpu.make_async_copy(
                    pool_hbm.at[layer_ref[0], table_ref[s, p]],
                    buf.at[slot, i], sems.at[slot])
                cp.wait() if wait else cp.start()

    n = blocks(b)
    nxt = jnp.minimum(b + 1, nseq - 1)
    n_next = jnp.where(b + 1 < nseq, blocks(nxt), 0)

    @pl.when(b == 0)
    def _():
        done_ref[0] = 0
        # pages never fetched into a slot must not feed NaN into p @ v
        buf[...] = jnp.zeros_like(buf)

        @pl.when(n > 0)
        def _():
            copies(0, 0, 0, wait=False)

    base = done_ref[0]                    # blocks walked by earlier steps

    @pl.when((n == 0) & (n_next > 0))
    def _():
        copies(nxt, 0, base % 2, wait=False)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    length = lens_ref[b]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    col_page = col // page
    q = q_ref[0]                                           # (H, R)

    def step(i, carry):
        slot = (base + i) % 2

        @pl.when(i + 1 < n)
        def _():
            copies(b, i + 1, 1 - slot, wait=False)

        @pl.when((i + 1 == n) & (n_next > 0))
        def _():
            copies(nxt, 0, 1 - slot, wait=False)

        copies(b, i, slot, wait=True)
        dead = 0                          # bit j: page j not fetched
        for j in range(ppb):
            dead |= jnp.where(live(b, i * ppb + j), 0, 1 << j)
        valid = ((i * bk + col < length)
                 & (((dead >> col_page) & 1) == 0))        # (1, bk)

        rows = buf[slot].reshape(bk, -1)                   # (bk, R)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (H, bk)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_new), 0.0)
        pexp = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_ref[...] = l_ref[...] * alpha + pexp.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp.astype(rows.dtype), rows[:, :kv_lora],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (H, kv_lora)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n, step, 0)
    done_ref[0] = base + n
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("kv_lora", "scale", "interpret"))
def paged_latent_attention_pallas(q: jnp.ndarray, pool: jnp.ndarray,
                                  block_table: jnp.ndarray,
                                  lengths: jnp.ndarray, layer=0, *,
                                  kv_lora: int, scale: float,
                                  interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, R); pool: (N, page, R) or (L, N, page, R) with ``layer``
    the one to read; block_table: (B, MAXP).

    Returns (B, H, kv_lora).  ``interpret=True`` runs the kernel body on
    the CPU for validation; on a TPU it compiles to Mosaic.
    """
    if pool.ndim == 3:
        pool = pool[None]
    b, h, r = q.shape
    page = pool.shape[2]
    maxp = block_table.shape[1]
    ppb = block_pages(page, maxp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, r), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, kv_lora), lambda s, *_: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, r), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, kv_lora), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, page=page, ppb=ppb, kv_lora=kv_lora,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, kv_lora), q.dtype),
        # the grid carries the double buffer from one sequence to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the kernel's own name in compiled programs and device traces
        name="paged_latent_attention_kernel",
    )(block_table, lengths, jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      q, pool)

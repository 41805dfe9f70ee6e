"""Pure-jnp oracles for every Pallas kernel (self-contained, no model deps).

These are the ground truth for the per-kernel allclose sweeps in tests/.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.parallel.context import BATCH, constrain_act

NEG_INF = -1e30


def paged_attention_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                        v_pages: jnp.ndarray, block_table: jnp.ndarray,
                        valid_lens: jnp.ndarray, layer=0, *,
                        window: int = 0) -> jnp.ndarray:
    """Decode attention over paged KV.

    q: (B, 1, H, D) one query token per sequence
    k_pages/v_pages: (N, page, K, D) physical pools, or (L, N, page, K, D)
      of which ``layer`` is read
    block_table: (B, max_pages) int32 physical page ids (-1 = unmapped)
    valid_lens: (B,) number of attendable tokens (incl. the new one)
    window: if > 0, only the last `window` tokens are attendable.
    Returns (B, 1, H, D).
    """
    if k_pages.ndim == 5:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
    b, s1, h, d = q.shape
    n, page, kh, _ = k_pages.shape
    g = h // kh
    maxp = block_table.shape[1]
    safe = jnp.maximum(block_table, 0)
    ks = k_pages[safe].reshape(b, maxp * page, kh, d)
    vs = v_pages[safe].reshape(b, maxp * page, kh, d)
    # keep the gathered KV in the pools' sharding (kv-heads or head-dim on
    # "model"); the score einsum then psums small f32 scores instead of
    # all-gathering the cache (H3)
    ks = constrain_act(ks, BATCH, None, "model", "model")
    vs = constrain_act(vs, BATCH, None, "model", "model")

    qg = q.reshape(b, s1, kh, g, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, ks,
                        preferred_element_type=jnp.float32)
    scores = constrain_act(scores / math.sqrt(d), BATCH, "model", None,
                           None, None)
    kpos = jnp.arange(maxp * page)
    mask = kpos[None, :] < valid_lens[:, None]
    if window > 0:
        mask &= kpos[None, :] >= (valid_lens[:, None] - window)
    mask &= (block_table >= 0).repeat(page, axis=1)
    scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w.astype(vs.dtype), vs,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s1, h, d).astype(q.dtype)


def paged_latent_attention_ref(q: jnp.ndarray, pool: jnp.ndarray,
                               block_table: jnp.ndarray,
                               valid_lens: jnp.ndarray, layer=0, *,
                               kv_lora: int, scale: float) -> jnp.ndarray:
    """Absorbed latent-attention decode over a paged latent pool.

    q: (B, H, R) queries against whole rows; pool: (N, page, R) rows of
    [c_kv (kv_lora) | rotary key | padding], or (L, N, page, R) of which
    ``layer`` is read; block_table: (B, max_pages) (-1 = unmapped);
    valid_lens: (B,) attendable tokens.  Each head scores every row and
    averages the rows' first ``kv_lora`` lanes.  Returns (B, H, kv_lora).
    """
    if pool.ndim == 4:
        pool = pool[layer]
    b, h, r = q.shape
    page = pool.shape[1]
    maxp = block_table.shape[1]
    rows = pool[jnp.maximum(block_table, 0)].reshape(b, maxp * page, r)
    s = jnp.einsum("bhr,btr->bht", q, rows,
                   preferred_element_type=jnp.float32) * scale
    kpos = jnp.arange(maxp * page)
    mask = kpos[None, :] < valid_lens[:, None]
    mask &= (block_table >= 0).repeat(page, axis=1)
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bht,btc->bhc", w, rows[..., :kv_lora],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, causal: bool = True, window: int = 0
                        ) -> jnp.ndarray:
    """Full masked softmax attention in f32 (training oracle).

    q: (B, S, H, D); k/v: (B, S, K, D) with GQA grouping H = G*K.
    """
    b, s, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    qg = q.reshape(b, s, kh, g, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    qpos = jnp.arange(s)
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= qpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= qpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    # rows that are fully masked produce uniform weights; zero them
    any_valid = mask.any(axis=1)
    w = jnp.where(any_valid[None, None, None, :, None], w, 0.0)
    out = jnp.einsum("bkgst,btkd->bskgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, d).astype(q.dtype)


def page_gather_ref(pages: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Materialize logical sequences from paged storage.

    pages: (N, page, F); table: (B, max_pages) -> (B, max_pages*page, F)."""
    safe = jnp.maximum(table, 0)
    b, mp = table.shape
    n, pg, f = pages.shape
    out = pages[safe].reshape(b, mp * pg, f)
    valid = (table >= 0).repeat(pg, axis=1)
    return jnp.where(valid[..., None], out, 0)

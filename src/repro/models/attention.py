"""Attention variants: GQA/MQA/MHA, sliding-window, MLA, cross-attention.

Three execution regimes:
  * full/blockwise training & prefill (causal or windowed)
  * dense-cache decode (contiguous KV cache, the "ideal/no-translation" mode)
  * paged-cache decode lives in repro.serving / repro.kernels (NDPage path)

All softmax math in f32; blockwise (flash-style) attention is the default
above ``BLOCKWISE_THRESHOLD`` so 32k prefill never materializes S^2 scores.
"""
from __future__ import annotations

import functools
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import block_table as BT
from repro.core import kv_page_manager as KVM
from repro.models.layers import (apply_rope, dense_init, rmsnorm,
                                 rmsnorm_init, yarn_mscale)
from repro.parallel.context import BATCH, constrain_act

Params = Dict[str, Any]

BLOCKWISE_THRESHOLD = 2048
Q_CHUNK = 1024
KV_CHUNK = 1024
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def attn_init(key, cfg, dtype) -> Params:
    d, h, k, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, h * hd, dtype),
        "wk": dense_init(ks[1], d, k * hd, dtype),
        "wv": dense_init(ks[2], d, k * hd, dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype),
    }


def mla_init(key, cfg, dtype) -> Params:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "w_dq": dense_init(ks[0], d, m.q_lora_rank, dtype),
        "q_norm": rmsnorm_init(m.q_lora_rank, dtype),
        "w_uq": dense_init(ks[1], m.q_lora_rank, h * qk, dtype),
        "w_dkv": dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim, dtype),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dtype),
        "w_uk": dense_init(ks[3], m.kv_lora_rank, h * m.qk_nope_head_dim, dtype),
        "w_uv": dense_init(ks[4], m.kv_lora_rank, h * m.v_head_dim, dtype),
        "wo": dense_init(ks[5], h * m.v_head_dim, d, dtype),
    }


# ---------------------------------------------------------------------------
# masked "naive" attention (short sequences, and the oracle for blockwise)
# ---------------------------------------------------------------------------
def _gqa_scores_attend(q, k, v, mask, scale):
    """q: (B,Sq,H,D) k,v: (B,Skv,K,D) mask: (B|1, Sq, Skv) bool."""
    b, sq, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    q = q.reshape(b, sq, kh, g, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def full_attention(q, k, v, *, causal: bool, window: int = 0,
                   q_offset=0, kv_valid_len=None) -> jnp.ndarray:
    """Masked softmax attention. q:(B,Sq,H,D), k/v:(B,Skv,K,D).

    ``q_offset``: absolute position of q[0] (decode / chunked prefill).
    ``window``: if >0, keys older than ``window`` positions are masked.
    ``kv_valid_len``: (B,) number of valid cache slots (decode).
    """
    b, sq = q.shape[:2]
    skv = k.shape[1]
    qpos = jnp.arange(sq) + q_offset                    # (Sq,)
    kpos = jnp.arange(skv)                              # (Skv,)
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= kpos[None, :] > qpos[:, None] - window
    mask = jnp.broadcast_to(mask[None], (b, sq, skv))
    if kv_valid_len is not None:
        mask &= kpos[None, None, :] < kv_valid_len[:, None, None]
    scale = 1.0 / math.sqrt(q.shape[-1])
    return _gqa_scores_attend(q, k, v, mask, scale)


# ---------------------------------------------------------------------------
# blockwise (flash-style) attention in pure JAX — memory O(chunk^2)
# ---------------------------------------------------------------------------
def blockwise_attention(q, k, v, *, causal: bool, window: int = 0,
                        q_chunk: int = Q_CHUNK, kv_chunk: int = KV_CHUNK
                        ) -> jnp.ndarray:
    """Online-softmax chunked attention (the pure-jnp flash oracle).

    q: (B,S,H,D), k/v: (B,S,K,D); self-attention with optional causal /
    sliding-window masking.  Never materializes more than
    (q_chunk x kv_chunk) scores per head.
    """
    b, s, h, d = q.shape
    kh = k.shape[2]
    g = h // kh
    scale = 1.0 / math.sqrt(d)
    assert s % q_chunk == 0 and s % kv_chunk == 0, (s, q_chunk, kv_chunk)
    nq, nk = s // q_chunk, s // kv_chunk

    qc = q.reshape(b, nq, q_chunk, kh, g, d)
    kc = k.reshape(b, nk, kv_chunk, kh, d)
    vc = v.reshape(b, nk, kv_chunk, kh, d)

    qpos = jnp.arange(s).reshape(nq, q_chunk)
    kpos = jnp.arange(s).reshape(nk, kv_chunk)

    def q_block(qi, q_i):
        # q_i: (B, qc, K, G, D)
        m0 = jnp.full((b, kh, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kh, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, kh, g, q_chunk, d), jnp.float32)

        def kv_block(carry, inp):
            m, l, acc = carry
            kj, k_j, v_j = inp
            sc = jnp.einsum("bskgd,btkd->bkgst", q_i, k_j,
                            preferred_element_type=jnp.float32) * scale
            msk = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                msk &= kpos[kj][None, :] <= qpos[qi][:, None]
            if window > 0:
                msk &= kpos[kj][None, :] > qpos[qi][:, None] - window
            sc = jnp.where(msk[None, None, None], sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(axis=-1))
            # guard fully-masked rows
            p = jnp.exp(sc - m_new[..., None])
            p = jnp.where(jnp.isfinite(sc), p, 0.0)
            corr = jnp.exp(m - m_new)
            corr = jnp.where(jnp.isfinite(m), corr, 0.0)
            l = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(v_j.dtype), v_j,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (m_new, l, acc), None

        (m, l, acc), _ = jax.lax.scan(
            kv_block, (m0, l0, a0),
            (jnp.arange(nk), kc.swapaxes(0, 1), vc.swapaxes(0, 1)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 3, 1, 2, 4)  # (B, qc, K, G, D)

    out = jax.lax.map(lambda qi: q_block(qi, qc[:, qi]), jnp.arange(nq))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, h, d)
    return out.astype(q.dtype)


def self_attention(q, k, v, *, causal: bool = True, window: int = 0
                   ) -> jnp.ndarray:
    if q.shape[1] > BLOCKWISE_THRESHOLD and q.shape[1] == k.shape[1]:
        # recompute-in-backward (flash-attention memory discipline): the
        # O(chunk^2) f32 score blocks are never stored as residuals —
        # only q/k/v are. On TPU the Pallas kernel implements the same.
        fn = jax.checkpoint(
            functools.partial(blockwise_attention, causal=causal,
                              window=window))
        return fn(q, k, v)
    return full_attention(q, k, v, causal=causal, window=window)


# ---------------------------------------------------------------------------
# GQA layer: train / prefill
# ---------------------------------------------------------------------------
def attn_apply(params: Params, x: jnp.ndarray, positions: jnp.ndarray, cfg,
               *, window: int = 0, causal: bool = True,
               return_kv: bool = False):
    b, s, d = x.shape
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(b, s, h, hd)
    k = (x @ params["wk"]).reshape(b, s, kh, hd)
    v = (x @ params["wv"]).reshape(b, s, kh, hd)
    q = constrain_act(q, BATCH, None, "model", None)
    k = constrain_act(k, BATCH, None, "model", None)
    v = constrain_act(v, BATCH, None, "model", None)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    out = self_attention(q, k, v, causal=causal, window=window)
    out = constrain_act(out, BATCH, None, "model", None)
    y = out.reshape(b, s, h * hd) @ params["wo"]
    if return_kv:
        return y, (k, v)
    return y


def cross_attn_apply(params: Params, x: jnp.ndarray,
                     enc_k: jnp.ndarray, enc_v: jnp.ndarray, cfg):
    """Decoder cross-attention over precomputed encoder K/V (no mask)."""
    b, s, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(b, s, h, hd)
    out = full_attention(q, enc_k, enc_v, causal=False)
    return out.reshape(b, s, h * hd) @ params["wo"]


def cross_kv(params: Params, enc_out: jnp.ndarray, cfg):
    b, se, _ = enc_out.shape
    kh, hd = cfg.num_kv_heads, cfg.head_dim
    k = (enc_out @ params["wk"]).reshape(b, se, kh, hd)
    v = (enc_out @ params["wv"]).reshape(b, se, kh, hd)
    return k, v


# ---------------------------------------------------------------------------
# GQA layer: dense-cache decode  (cache: (B, S_max, K, D))
# ---------------------------------------------------------------------------
def attn_decode_dense(params: Params, x: jnp.ndarray, cache_k, cache_v,
                      lengths: jnp.ndarray, cfg, *, window: int = 0):
    """One-token decode against a contiguous KV cache.

    x: (B, 1, D); lengths: (B,) tokens already in cache (the new token is
    written at index ``lengths``).  Returns (y, new_cache_k, new_cache_v).
    """
    b, s1, d = x.shape
    assert s1 == 1
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(b, 1, h, hd)
    k = (x @ params["wk"]).reshape(b, 1, kh, hd)
    v = (x @ params["wv"]).reshape(b, 1, kh, hd)
    pos = lengths[:, None]                               # (B,1)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)

    bidx = jnp.arange(b)
    cache_k = cache_k.at[bidx, lengths].set(k[:, 0])
    cache_v = cache_v.at[bidx, lengths].set(v[:, 0])

    skv = cache_k.shape[1]
    kpos = jnp.arange(skv)
    mask = kpos[None, None, :] < (lengths + 1)[:, None, None]
    if window > 0:
        mask &= kpos[None, None, :] > lengths[:, None, None] - window
    out = _gqa_scores_attend(q, cache_k, cache_v, mask, 1.0 / math.sqrt(hd))
    y = out.reshape(b, 1, h * hd) @ params["wo"]
    return y, cache_k, cache_v


# ---------------------------------------------------------------------------
# GQA layer: paged-cache decode (the NDPage path)
# ---------------------------------------------------------------------------
def attn_decode_paged(params: Params, x: jnp.ndarray, kp, vp, table,
                      lengths: jnp.ndarray, cfg, *, window: int = 0,
                      mode: str = BT.FLAT, layer=None):
    """One-token decode against paged KV pools.

    kp/vp: (N_pages, page, K, D) pools, or the pools of a stack's scanned
    layers (L, N_pages, page, K, D) with ``layer`` this layer's index: the
    new token is appended in place and the kernel reads the layer's pages
    out of the stack at that index.  ``table`` is a flat (B, max_pages)
    map (NDPage) or a RadixTable (2-level baseline).  The table translate
    is the address-translation step; flat mode does ONE indirection, radix
    TWO.  Returns (y, kp, vp).

    With a mesh installed (repro.parallel.context) the data path runs under
    shard_map with SHARD-LOCAL paging (perf iteration H4): sequences are
    scheduler-affine to their data shard, table values are local page ids,
    the pool gather never crosses shards, and only the small f32 score
    partials cross the model axis (head_dim-sharded pools).  Without a mesh
    the XLA reference path runs (CPU engine / smoke tests).
    """
    from repro.parallel.context import current_mesh

    b = x.shape[0]
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    page = kp.shape[-3]
    q = (x @ params["wq"]).reshape(b, 1, h, hd)
    k = (x @ params["wk"]).reshape(b, 1, kh, hd)
    v = (x @ params["wv"]).reshape(b, 1, kh, hd)
    pos = lengths[:, None]
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)

    # translation (metadata path)
    phys_all = BT.translate_all(table, mode)              # (B, max_pages)

    mesh = current_mesh()
    if mesh is not None:
        kl, vl = (kp, vp) if layer is None else (kp[layer], vp[layer])
        out, kl, vl = _paged_attend_shardmap(
            mesh, q, k[:, 0], v[:, 0], kl, vl, phys_all, lengths,
            window=window, cfg=cfg)
        kp, vp = ((kl, vl) if layer is None else
                  (kp.at[layer].set(kl), vp.at[layer].set(vl)))
    else:
        bidx = jnp.arange(b)
        logical = lengths // page
        phys_new = phys_all[bidx, logical]
        kp, vp = KVM.append_kv(kp, vp, k[:, 0], v[:, 0],
                               jnp.maximum(phys_new, 0), lengths % page,
                               layer=layer)
        from repro.kernels import ops as KOPS
        out = KOPS.paged_attention(q, kp, vp, phys_all, lengths + 1,
                                   0 if layer is None else layer,
                                   window=window)
    # contract (heads, head_dim) against wo without flattening so an
    # hd-sharded attention output psums once into (B, 1, D)
    wo3 = params["wo"].reshape(h, hd, cfg.d_model)
    y = jnp.einsum("bshd,hdD->bsD", out, wo3)
    return y, kp, vp


def _paged_attend_shardmap(mesh, q, k_new, v_new, kp, vp, phys_all, lengths,
                           *, window: int, cfg):
    """Shard-local paged append+attend (see attn_decode_paged docstring).

    q: (B,1,H,hd); k_new/v_new: (B,K,hd); kp/vp: (N,page,K,hd);
    phys_all: (B,maxp) SHARD-LOCAL page ids; lengths: (B,).
    Pools shard N->batch axes and hd->model; q/out shard hd->model.
    """
    from jax.sharding import PartitionSpec as P
    from repro.parallel.sharding import batch_axes

    import numpy as _np
    axes = batch_axes(mesh)
    dp_size = int(_np.prod([mesh.shape[a] for a in axes])) if axes else 1
    # sequences are shard-affine: only shard the batch if it divides (e.g.
    # long_500k's batch=1 keeps its pool whole and relies on the model axis)
    dp = axes if (axes and q.shape[0] % dp_size == 0) else None
    hd = q.shape[-1]
    n_model = mesh.shape["model"]
    page = kp.shape[1]
    h = q.shape[2]
    kh = kp.shape[2]

    # strategy choice (perf iteration H5): for MQA/small-K archs whose pool
    # fits per-device when replicated over the model axis, shard the QUERY
    # heads over "model" — each model shard runs softmax locally, zero score
    # psum.  Otherwise shard head_dim and psum the f32 score partials.
    # MUST agree with parallel.sharding's pool storage rule.
    from repro.parallel.sharding import _n_attn_layers, qhead_strategy
    kv_ok = kh % n_model == 0
    q_head_mode = (not kv_ok) and qhead_strategy(
        mesh, h=h, kh=kh, hd=hd, n_attn_layers=_n_attn_layers(cfg),
        n_pages=kp.shape[0], page=page)
    if q_head_mode:
        md = None
        qspec = P(dp, None, "model", None)
    else:
        md = "model" if hd % n_model == 0 else None
        qspec = P(dp, None, None, md)

    g_global = max(h // kh, 1)

    def local(q_l, kn_l, vn_l, kp_l, vp_l, tab_l, len_l, *,
              select_kv: bool = False):
        bl = q_l.shape[0]
        bidx = jnp.arange(bl)
        logical = len_l // page
        phys_new = jnp.maximum(tab_l[bidx, logical], 0)
        kp_l = kp_l.at[phys_new, len_l % page].set(kn_l)
        vp_l = vp_l.at[phys_new, len_l % page].set(vn_l)

        safe = jnp.maximum(tab_l, 0)
        maxp = tab_l.shape[1]
        kh_ = kp_l.shape[2]
        hdl = kp_l.shape[3]
        hq = q_l.shape[2]
        ks = kp_l[safe].reshape(bl, maxp * page, kh_, hdl)
        vs = vp_l[safe].reshape(bl, maxp * page, kh_, hdl)
        if select_kv:
            # q-head mode with grouped KV: pick each local q head's kv head
            # from the replicated pool (local heads may straddle groups)
            head_ids = jax.lax.axis_index("model") * hq + jnp.arange(hq)
            kv_ids = head_ids // g_global
            ks = ks[:, :, kv_ids, :]           # (bl, T, hq, hd)
            vs = vs[:, :, kv_ids, :]
            kh_, g = hq, 1
        else:
            g = max(hq // kh_, 1)
        qg = q_l.reshape(bl, 1, kh_, g, hdl)
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, ks,
                            preferred_element_type=jnp.float32)
        if not q_head_mode and md is not None:
            # partial over hd shards -> explicit small psum
            scores = jax.lax.psum(scores, "model")
        scores = scores / math.sqrt(hd)
        kpos = jnp.arange(maxp * page)
        valid = kpos[None, :] < (len_l + 1)[:, None]
        if window > 0:
            valid &= kpos[None, :] >= (len_l + 1 - window)[:, None]
        valid &= (tab_l >= 0).repeat(page, axis=1)
        scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgst,btkd->bskgd", w.astype(vs.dtype), vs,
                         preferred_element_type=jnp.float32)
        return (out.reshape(bl, 1, hq, hdl).astype(q_l.dtype), kp_l, vp_l)

    if kv_ok:
        # kv heads divide the model axis: shard K (and the aligned q-head
        # groups); attention fully local per shard
        in_specs = (P(dp, None, "model", None), P(dp, "model", None),
                    P(dp, "model", None), P(dp, None, "model", None),
                    P(dp, None, "model", None), P(dp, None), P(dp))
        out_specs = (P(dp, None, "model", None), P(dp, None, "model", None),
                     P(dp, None, "model", None))
        fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    elif q_head_mode:
        # query heads shard over "model": each shard sees ALL kv heads of
        # its sequences (pool replicated over model) and h/16 query heads
        def local_qh(q_l, kn_l, vn_l, kp_l, vp_l, tab_l, len_l):
            return local(q_l, kn_l, vn_l, kp_l, vp_l, tab_l, len_l,
                         select_kv=True)
        in_specs = (qspec, P(dp, None, None), P(dp, None, None),
                    P(dp, None, None, None), P(dp, None, None, None),
                    P(dp, None), P(dp))
        out_specs = (P(dp, None, "model", None), P(dp, None, None, None),
                     P(dp, None, None, None))
        fn = jax.shard_map(local_qh, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    else:
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(qspec, P(dp, None, md), P(dp, None, md),
                      P(dp, None, None, md), P(dp, None, None, md),
                      P(dp, None), P(dp)),
            out_specs=(P(dp, None, None, md), P(dp, None, None, md),
                       P(dp, None, None, md)),
            check_vma=False)
    return fn(q, k_new, v_new, kp, vp, phys_all, lengths)


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------
def latent_row(cfg) -> int:
    """Lanes of one cached token in a latent page: ``c_kv``, then the
    rotary key, zero-padded to whole 128-lane tiles (the TPU's HBM tiling
    pads a row to them anyway, and the kernel DMAs whole tiles)."""
    m = cfg.mla
    return -(-(m.kv_lora_rank + m.qk_rope_head_dim) // 128) * 128


def mla_softmax_scale(cfg) -> float:
    """1/sqrt(qk head dim), times YaRN's mscale(mscale_all_dim)^2."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _mla_qkv_full(params, x, positions, cfg):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    cq = rmsnorm(params["q_norm"], x @ params["w_dq"], cfg.rms_norm_eps)
    q = (cq @ params["w_uq"]).reshape(
        b, s, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)

    dkv = x @ params["w_dkv"]
    c_kv, k_rope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(params["kv_norm"], c_kv, cfg.rms_norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        cfg.rope_scaling)
    return q_nope, q_rope, c_kv, k_rope[:, :, 0, :]


def mla_apply(params: Params, x: jnp.ndarray, positions, cfg,
              *, causal: bool = True):
    """MLA train/prefill: expand latent to per-head K/V and attend."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_full(params, x, positions, cfg)
    q_nope = constrain_act(q_nope, BATCH, None, "model", None)
    k_nope = (c_kv @ params["w_uk"]).reshape(b, s, h, m.qk_nope_head_dim)
    k_nope = constrain_act(k_nope, BATCH, None, "model", None)
    v = (c_kv @ params["w_uv"]).reshape(b, s, h, m.v_head_dim)
    v = constrain_act(v, BATCH, None, "model", None)
    # fold rope part into an extended head dim so one attention does both
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, h, m.qk_rope_head_dim))], axis=-1)
    # self_attention scales by 1/sqrt(qk dim); YaRN's mscale^2 rides on q
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    out = self_attention(q_full * (mla_softmax_scale(cfg) * math.sqrt(qk_dim)),
                         k_full, v_padded(v, k_full.shape[-1]),
                         causal=causal)
    out = out[..., : m.v_head_dim]
    return out.reshape(b, s, h * m.v_head_dim) @ params["wo"]


def v_padded(v, dim):
    pad = dim - v.shape[-1]
    if pad <= 0:
        return v
    return jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))


def _absorbed_q(params, q_nope, cfg):
    """q_nope (B, 1, H, nope) with W_uk absorbed: (B, H, kv_lora) f32."""
    m = cfg.mla
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, cfg.num_heads,
                                  m.qk_nope_head_dim)
    return jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w_uk,
                      preferred_element_type=jnp.float32)


def _mla_out(params, ctx, x, cfg):
    """Latent context (B, H, kv_lora) through W_uv and wo: (B, 1, D)."""
    m = cfg.mla
    b, h = ctx.shape[:2]
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bhc,chv->bhv", ctx, w_uv.astype(ctx.dtype),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h * m.v_head_dim).astype(x.dtype) @ params["wo"]


def mla_decode(params: Params, x: jnp.ndarray, cache_ckv, cache_krope,
               lengths, cfg):
    """Absorbed-matrix MLA decode: attends in the 512-dim latent space.

    cache_ckv: (B, S_max, kv_lora); cache_krope: (B, S_max, rope_dim).
    """
    b = x.shape[0]
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_full(params, x, lengths[:, None],
                                                 cfg)
    bidx = jnp.arange(b)
    cache_ckv = cache_ckv.at[bidx, lengths].set(c_kv[:, 0])
    cache_krope = cache_krope.at[bidx, lengths].set(k_rope[:, 0])

    q_lat = _absorbed_q(params, q_nope, cfg)               # (B, H, kv_lora)
    scores = (
        jnp.einsum("bhc,btc->bht", q_lat, cache_ckv.astype(jnp.float32),
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bhr,btr->bht", q_rope[:, 0].astype(jnp.float32),
                     cache_krope.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    ) * mla_softmax_scale(cfg)
    skv = cache_ckv.shape[1]
    mask = jnp.arange(skv)[None, None, :] < (lengths + 1)[:, None, None]
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bht,btc->bhc", w, cache_ckv.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return _mla_out(params, ctx, x, cfg), cache_ckv, cache_krope


def mla_decode_paged(params: Params, x: jnp.ndarray, pool, table, lengths,
                     cfg, *, mode: str = BT.FLAT, layer=None):
    """Absorbed-matrix MLA decode against a paged latent pool.

    pool: (N_pages, page, latent_row(cfg)), rows of [c_kv | k_rope | 0],
    or the pools of a stack's scanned layers (L, N_pages, page, row) with
    ``layer`` this layer's index: appended and read in place, never
    sliced out.  ``table`` is the flat (B, max_pages) map or a
    RadixTable, translated once as for GQA.  The new token's row is
    appended into the page the table maps; each head scores every row
    with W_uk absorbed into its query (paged_latent_attention), then W_uv
    and wo apply.  Returns (y, pool).
    """
    from repro.kernels import ops as KOPS
    m = cfg.mla
    b = x.shape[0]
    page = pool.shape[-2]
    pad = pool.shape[-1] - m.kv_lora_rank - m.qk_rope_head_dim
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_full(params, x, lengths[:, None],
                                                 cfg)
    phys_all = BT.translate_all(table, mode)              # (B, max_pages)
    row = jnp.concatenate(
        [c_kv[:, 0], k_rope[:, 0], jnp.zeros((b, pad), c_kv.dtype)], -1)
    phys_new = jnp.maximum(phys_all[jnp.arange(b), lengths // page], 0)
    pool = KVM.append_rows(pool, row.astype(pool.dtype), phys_new,
                           lengths % page, layer=layer)
    q_lat = _absorbed_q(params, q_nope, cfg)
    q = jnp.concatenate(
        [q_lat, q_rope[:, 0].astype(jnp.float32),
         jnp.zeros(q_lat.shape[:2] + (pad,), jnp.float32)], -1)
    ctx = KOPS.paged_latent_attention(
        q.astype(pool.dtype), pool, phys_all, lengths + 1,
        0 if layer is None else layer, kv_lora=m.kv_lora_rank,
        scale=mla_softmax_scale(cfg))
    return _mla_out(params, ctx, x, cfg), pool

"""Block + stack assembly with scan-over-periods.

A stack = ``prefix`` blocks (unrolled) + N periods of ``layer_pattern``
(lax.scan over stacked params) — HLO size stays O(period), not O(depth),
which keeps 60-88 layer archs compilable in bounded time/memory.

Decode state mirrors the params tree: {"prefix": [block_state...],
"scan": period_state stacked over periods}.  KV backends:
  dense       contiguous per-layer KV cache (the no-translation baseline)
  paged_flat  NDPage flattened single-level block table (one indirection)
  paged_radix 2-level directory->leaf block table (two indirections)
Latent attention (MLA) pages one row a token, shared by every head, in a
pool of its own per layer; GQA pages K and V.  The paged pools of the
scanned layers ride the scan's carry whole, stacked over periods, and
each layer appends its new token into its own in place by its index
(the decode state is donated), so no layer writes a pool back.

In decode, latent attention runs under the named scope ``mla`` and a
routed expert layer under ``moe``, which the compiled step's op metadata
carries to a device trace's reader.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import config as C
from repro.core import block_table as BT
from repro.core import kv_page_manager as KVM
from repro.models import attention as A
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models import rwkv as R
from repro.models.layers import (dtype_of, ffn_apply, ffn_init,
                                 relu_sq_ffn_apply, relu_sq_ffn_init,
                                 rmsnorm, rmsnorm_init)
from repro.parallel.context import BATCH, constrain_act

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------
def block_init(key, cfg, mixer_kind: str, ffn_kind: str,
               cross: bool = False) -> Params:
    dt = dtype_of(cfg)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p: Params = {"norm1": rmsnorm_init(d, dt), "norm2": rmsnorm_init(d, dt)}
    if mixer_kind in (C.ATTN, C.ATTN_LOCAL):
        p["mixer"] = A.attn_init(ks[0], cfg, dt)
    elif mixer_kind == C.ATTN_MLA:
        p["mixer"] = A.mla_init(ks[0], cfg, dt)
    elif mixer_kind == C.MAMBA:
        p["mixer"] = M.mamba_init(ks[0], cfg, dt)
    elif mixer_kind == C.RWKV:
        p["mixer"] = R.rwkv_init(ks[0], cfg, dt)
    else:
        raise ValueError(mixer_kind)
    if ffn_kind == C.MOE_FF:
        p["ffn"] = MOE.moe_init(ks[1], cfg, dt)
    elif cfg.rwkv is not None:
        p["ffn"] = relu_sq_ffn_init(ks[1], d, cfg.d_ff, dt)
    else:
        p["ffn"] = ffn_init(ks[1], d, cfg.d_ff, dt, cfg.gated_ffn)
    if cross:
        p["norm_cross"] = rmsnorm_init(d, dt)
        p["cross"] = A.attn_init(ks[2], cfg, dt)
    return p


def _apply_ffn(bp: Params, h: jnp.ndarray, cfg, ffn_kind: str,
               shift_prev: Optional[jnp.ndarray] = None):
    """Returns (y, aux)."""
    if ffn_kind == C.MOE_FF:
        return MOE.moe_apply(bp["ffn"], h, cfg)[:2]
    if cfg.rwkv is not None:
        if shift_prev is None:  # train: shift along seq
            prev = jnp.pad(h, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        else:
            prev = shift_prev[:, None].astype(h.dtype)
        return relu_sq_ffn_apply(bp["ffn"], h, prev), jnp.float32(0)
    return ffn_apply(bp["ffn"], h, cfg.gated_ffn), jnp.float32(0)


def block_apply_train(bp: Params, x: jnp.ndarray, positions, cfg,
                      mixer_kind: str, ffn_kind: str,
                      enc_out=None, causal: bool = True):
    x = constrain_act(x, BATCH, None, None)
    h = rmsnorm(bp["norm1"], x, cfg.rms_norm_eps)
    if mixer_kind == C.ATTN:
        y = A.attn_apply(bp["mixer"], h, positions, cfg, causal=causal)
    elif mixer_kind == C.ATTN_LOCAL:
        y = A.attn_apply(bp["mixer"], h, positions, cfg,
                         window=cfg.window_size, causal=causal)
    elif mixer_kind == C.ATTN_MLA:
        y = A.mla_apply(bp["mixer"], h, positions, cfg, causal=causal)
    elif mixer_kind == C.MAMBA:
        y = M.mamba_apply(bp["mixer"], h, cfg)
    elif mixer_kind == C.RWKV:
        y = R.rwkv_apply(bp["mixer"], h, cfg)
    else:
        raise ValueError(mixer_kind)
    x = constrain_act(x + y, BATCH, None, None)
    if enc_out is not None and "cross" in bp:
        hc = rmsnorm(bp["norm_cross"], x, cfg.rms_norm_eps)
        ek, ev = A.cross_kv(bp["cross"], enc_out, cfg)
        x = x + A.cross_attn_apply(bp["cross"], hc, ek, ev, cfg)
    h2 = rmsnorm(bp["norm2"], x, cfg.rms_norm_eps)
    y2, aux = _apply_ffn(bp, h2, cfg, ffn_kind)
    return constrain_act(x + y2, BATCH, None, None), aux


# ---------------------------------------------------------------------------
# decode state per block
# ---------------------------------------------------------------------------
def block_init_state(cfg, mixer_kind: str, ffn_kind: str, batch: int,
                     max_len: int, kv_mode: str, page_size: int,
                     pages_per_layer: int):
    dt = dtype_of(cfg)
    st: Dict[str, Any] = {}
    if mixer_kind in (C.ATTN, C.ATTN_LOCAL):
        k, hd = cfg.num_kv_heads, cfg.head_dim
        if kv_mode == "dense":
            st["k"] = jnp.zeros((batch, max_len, k, hd), dt)
            st["v"] = jnp.zeros((batch, max_len, k, hd), dt)
        else:
            st["kp"] = jnp.zeros((pages_per_layer, page_size, k, hd), dt)
            st["vp"] = jnp.zeros((pages_per_layer, page_size, k, hd), dt)
    elif mixer_kind == C.ATTN_MLA:
        m = cfg.mla
        if kv_mode == "dense":
            st["ckv"] = jnp.zeros((batch, max_len, m.kv_lora_rank), dt)
            st["kr"] = jnp.zeros((batch, max_len, m.qk_rope_head_dim), dt)
        else:
            st["lat"] = jnp.zeros(
                (pages_per_layer, page_size, A.latent_row(cfg)), dt)
    elif mixer_kind == C.MAMBA:
        st.update(M.mamba_init_state(cfg, batch))
    elif mixer_kind == C.RWKV:
        st.update(R.rwkv_init_state(cfg, batch))
    if cfg.rwkv is not None and ffn_kind == C.DENSE_FF:
        st["ffn_shift"] = jnp.zeros((batch, cfg.d_model), jnp.float32)
    return st


def block_apply_decode(bp: Params, st, x, lengths, cfg,
                       mixer_kind: str, ffn_kind: str, kv_mode: str,
                       table=None, enc_out=None, layer=None):
    """x: (B,1,D). Returns (x', new_state, held expert rows or None).
    ``layer``: this layer's index in pools stacked over the scanned
    layers (``st["kp"]``, ``st["vp"]``, ``st["lat"]``)."""
    x = constrain_act(x, BATCH, None, None)
    h = rmsnorm(bp["norm1"], x, cfg.rms_norm_eps)
    new_st = dict(st)
    if mixer_kind in (C.ATTN, C.ATTN_LOCAL):
        window = cfg.window_size if mixer_kind == C.ATTN_LOCAL else 0
        if kv_mode == "dense":
            y, ck, cv = A.attn_decode_dense(
                bp["mixer"], h, st["k"], st["v"], lengths, cfg, window=window)
            new_st["k"], new_st["v"] = ck, cv
        else:
            y, kp, vp = A.attn_decode_paged(
                bp["mixer"], h, st["kp"], st["vp"], table, lengths, cfg,
                window=window, mode=kv_mode, layer=layer)
            new_st["kp"], new_st["vp"] = kp, vp
    elif mixer_kind == C.ATTN_MLA:
        with jax.named_scope("mla"):
            if kv_mode == "dense":
                y, ckv, kr = A.mla_decode(bp["mixer"], h, st["ckv"],
                                          st["kr"], lengths, cfg)
                new_st["ckv"], new_st["kr"] = ckv, kr
            else:
                y, new_st["lat"] = A.mla_decode_paged(
                    bp["mixer"], h, st["lat"], table, lengths, cfg,
                    mode=kv_mode, layer=layer)
    elif mixer_kind == C.MAMBA:
        y, ms = M.mamba_decode(bp["mixer"], h,
                               {"conv": st["conv"], "ssm": st["ssm"]}, cfg)
        new_st.update(ms)
    elif mixer_kind == C.RWKV:
        y, rs = R.rwkv_decode(bp["mixer"], h,
                              {"wkv": st["wkv"], "shift": st["shift"]}, cfg)
        new_st.update(rs)
    else:
        raise ValueError(mixer_kind)
    x = x + y
    if enc_out is not None and "cross" in bp:
        hc = rmsnorm(bp["norm_cross"], x, cfg.rms_norm_eps)
        ek, ev = A.cross_kv(bp["cross"], enc_out, cfg)
        x = x + A.cross_attn_apply(bp["cross"], hc, ek, ev, cfg)
    h2 = rmsnorm(bp["norm2"], x, cfg.rms_norm_eps)
    if ffn_kind == C.MOE_FF:
        with jax.named_scope("moe"):
            y2, _, rows = MOE.moe_apply(bp["ffn"], h2, cfg)
        return x + y2, new_st, rows
    shift_prev = st.get("ffn_shift")
    y2, _ = _apply_ffn(bp, h2, cfg, ffn_kind, shift_prev=shift_prev)
    if shift_prev is not None:
        new_st["ffn_shift"] = h2[:, 0].astype(jnp.float32)
    return x + y2, new_st, None


# ---------------------------------------------------------------------------
# stack
# ---------------------------------------------------------------------------
def stack_init(key, cfg, *, cross: bool = False) -> Params:
    """Params for prefix blocks + scanned periods."""
    kinds_prefix = list(cfg.prefix_pattern)
    pattern = list(cfg.layer_pattern)
    np_ = cfg.num_periods
    keys = jax.random.split(key, len(kinds_prefix) + np_ * len(pattern))
    prefix = [block_init(keys[i], cfg, mk, fk, cross)
              for i, (mk, fk) in enumerate(kinds_prefix)]
    base = len(kinds_prefix)

    def period_params(p):
        return {f"block_{j}": block_init(
            keys[base + p * len(pattern) + j], cfg, mk, fk, cross)
            for j, (mk, fk) in enumerate(pattern)}

    periods = [period_params(p) for p in range(np_)]
    scan = jax.tree.map(lambda *xs: jnp.stack(xs), *periods) if periods else {}
    return {"prefix": prefix, "scan": scan}


def stack_apply_train(params: Params, x, positions, cfg, *,
                      enc_out=None, causal: bool = True):
    """Returns (x, aux_sum). enc_out: encoder output for enc-dec stacks."""
    pattern = list(cfg.layer_pattern)
    aux = jnp.float32(0)
    for bp, (mk, fk) in zip(params["prefix"], cfg.prefix_pattern):
        x, a = block_apply_train(bp, x, positions, cfg, mk, fk, enc_out,
                                 causal)
        aux += a

    if cfg.num_periods == 0:
        return x, aux

    def period_body(carry, pp):
        x, aux = carry
        for j, (mk, fk) in enumerate(pattern):
            x, a = block_apply_train(pp[f"block_{j}"], x, positions, cfg,
                                     mk, fk, enc_out, causal)
            aux += a
        return (x, aux), None

    body = period_body
    if cfg.remat:
        body = jax.checkpoint(period_body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, aux), params["scan"])
    return x, aux


def stack_init_state(cfg, batch: int, max_len: int, kv_mode: str,
                     page_size: int, pages_per_layer: int):
    mk_state = lambda mk, fk: block_init_state(
        cfg, mk, fk, batch, max_len, kv_mode, page_size, pages_per_layer)
    prefix = [mk_state(mk, fk) for mk, fk in cfg.prefix_pattern]
    if cfg.num_periods == 0:
        return {"prefix": prefix, "scan": {}}
    period = {f"block_{j}": mk_state(mk, fk)
              for j, (mk, fk) in enumerate(cfg.layer_pattern)}
    scan = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.num_periods,) + a.shape),
        period)
    return {"prefix": prefix, "scan": scan}


#: decode-state entries that are paged pools: GQA's K and V, MLA's latent
PAGED_POOLS = ("kp", "vp", "lat")


def has_moe(cfg) -> bool:
    return any(fk == C.MOE_FF for _, fk in cfg.layer_kinds())


def stack_apply_decode(params: Params, state, x, lengths, cfg, *,
                       kv_mode: str, table=None, enc_out=None):
    """x: (B,1,D). Returns (x, new_state, rows): rows are the (token,
    expert) pairs the held routed experts computed, summed over layers,
    for a stack with expert layers, else None."""
    pattern = list(cfg.layer_pattern)
    moe = has_moe(cfg)
    rows = jnp.int32(0) if moe else None

    def add(total, r):
        return total + r if r is not None else total

    new_prefix = []
    for bp, st, (mk, fk) in zip(params["prefix"], state["prefix"],
                                cfg.prefix_pattern):
        x, nst, r = block_apply_decode(bp, st, x, lengths, cfg, mk, fk,
                                       kv_mode, table, enc_out)
        rows = add(rows, r)
        new_prefix.append(nst)

    if cfg.num_periods == 0:
        return x, {"prefix": new_prefix, "scan": {}}, rows

    # scanned paged pools are carried whole (see the module docstring)
    pools = {} if kv_mode == "dense" else {
        name: {k: v for k, v in st.items() if k in PAGED_POOLS}
        for name, st in state["scan"].items()}
    pools = {name: p for name, p in pools.items() if p}
    xs_state = {name: {k: v for k, v in st.items()
                       if k not in pools.get(name, {})}
                for name, st in state["scan"].items()}

    def period_body(carry, inp):
        x = carry["x"]
        rows = carry.get("rows")
        pools = {name: dict(p) for name, p in carry.get("pools", {}).items()}
        pp, pst = inp[:2]
        new_pst = {}
        for j, (mk, fk) in enumerate(pattern):
            name = f"block_{j}"
            own = pools.get(name, {})
            x, nst, r = block_apply_decode(
                pp[name], dict(pst[name], **own), x, lengths, cfg, mk, fk,
                kv_mode, table, enc_out, layer=inp[2] if own else None)
            rows = add(rows, r)
            for k in own:
                own[k] = nst.pop(k)
            new_pst[name] = nst
        out = {"x": x}
        if moe:
            out["rows"] = rows
        if pools:
            out["pools"] = pools
        return out, new_pst

    carry = {"x": x}
    if moe:
        carry["rows"] = rows
    xs = (params["scan"], xs_state)
    if pools:
        carry["pools"] = pools
        xs += (jnp.arange(cfg.num_periods),)
    carry, new_scan = jax.lax.scan(period_body, carry, xs)
    for name, p in carry.get("pools", {}).items():
        new_scan[name] = dict(new_scan[name], **p)
    return carry["x"], {"prefix": new_prefix, "scan": new_scan}, \
        carry.get("rows")

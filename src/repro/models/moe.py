"""Routed mixture-of-experts FFN (GShard-style grouped capacity dispatch).

Tokens are processed in fixed-size groups so the dispatch one-hots stay
O(group * E * C) instead of O(T^2) — this is what makes MoE shardable and
memory-bounded at 1M-token batches.  Experts shard over the `model` mesh
axis (expert parallelism); the dispatch einsums lower to all-to-alls under
pjit when tokens are data-sharded.

A layer may hold only a share of the routed experts (``MoEConfig.
first_expert`` / ``experts_held``, one chip of an expert-parallel
deployment): it routes over all of them with the published rule and
computes only its own experts' part, plus the shared experts.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init
from repro.parallel.context import BATCH, constrain_act

Params = Dict[str, Any]

GROUP_SIZE = 512  # tokens per routing group


def moe_init(key, cfg, dtype) -> Params:
    m = cfg.moe
    d = cfg.d_model
    dff = m.expert_d_ff or cfg.d_ff
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)

    def experts(k, d_in, d_out, n):
        return (jax.random.normal(k, (n, d_in, d_out), jnp.float32)
                * (1.0 / math.sqrt(d_in))).astype(dtype)

    p: Params = {
        "router": dense_init(ks[0], d, m.num_experts, jnp.float32, scale),
        "w_up": experts(ks[1], d, dff, m.held),
        "w_down": experts(ks[2], dff, d, m.held),
    }
    if cfg.gated_ffn:
        p["w_gate"] = experts(ks[3], d, dff, m.held)
    if m.num_shared_experts:
        sh = {}
        kk = jax.random.split(ks[4], 3)
        sdff = dff * m.num_shared_experts
        sh["w_up"] = dense_init(kk[0], d, sdff, dtype)
        sh["w_down"] = dense_init(kk[1], sdff, d, dtype)
        if cfg.gated_ffn:
            sh["w_gate"] = dense_init(kk[2], d, sdff, dtype)
        p["shared"] = sh
    return p


def _expert_ffn(p: Params, x: jnp.ndarray, gated: bool) -> jnp.ndarray:
    """x: (E, C, D) -> (E, C, D) with per-expert weights (E, D, F)."""
    up = jnp.einsum("ecd,edf->ecf", x, p["w_up"])
    if gated:
        gate = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", x, p["w_gate"]).astype(jnp.float32))
        h = (gate * up.astype(jnp.float32)).astype(x.dtype)
    else:
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def route(probs: jnp.ndarray, m) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The top-k experts of each token and their weights, (G, k) each.

    probs: (G, E) router softmax.  With groups (DeepSeek-V2's
    ``group_limited_greedy``), only experts of the ``topk_group`` groups
    whose best expert scores highest compete.  The weights are the
    chosen scores, renormalised to sum 1 or else scaled by
    ``routed_scaling_factor``.
    """
    g, e = probs.shape
    scores = probs
    if m.n_group > 1:
        best = probs.reshape(g, m.n_group, e // m.n_group).max(-1)
        _, top_g = jax.lax.top_k(best, m.topk_group)
        keep = jax.nn.one_hot(top_g, m.n_group, dtype=jnp.bool_).any(1)
        scores = jnp.where(jnp.repeat(keep, e // m.n_group, axis=1),
                           probs, 0.0)
    top_p, top_i = jax.lax.top_k(scores, m.num_experts_per_tok)
    if m.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    else:
        top_p = top_p * m.routed_scaling_factor
    return top_p, top_i


def _route_group(params: Params, xg: jnp.ndarray, cfg
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One routing group. xg: (G, D) -> (out (G, D), aux loss scalar,
    (token, expert) pairs the held experts computed)."""
    m = cfg.moe
    g, d = xg.shape
    e, k, held = m.num_experts, m.num_experts_per_tok, m.held
    # small groups (decode steps, smoke tests): exact dropless capacity;
    # large groups: capacity-factor routing (standard GShard behaviour)
    cap = g if g <= 64 else max(1, int(g * k * m.capacity_factor / e))

    logits = (xg.astype(jnp.float32) @ params["router"])          # (G, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = route(probs, m)                                # (G, k)

    # load-balance aux loss (Switch): E * mean(frac_tokens * frac_probs)
    onehot = jax.nn.one_hot(top_i, e, dtype=jnp.float32)          # (G, k, E)
    tokens_per_e = onehot.sum(axis=(0, 1)) / (g * k)
    probs_per_e = probs.mean(axis=0)
    aux = e * jnp.sum(tokens_per_e * probs_per_e)

    # the held experts' queues: position of each (token, slot) in its
    # expert's queue; slots routed to experts held elsewhere take none
    held_hot = jax.nn.one_hot(top_i - m.first_expert, held,
                              dtype=jnp.float32)                  # (G, k, H)
    flat = held_hot.reshape(g * k, held)
    pos_in_e = jnp.cumsum(flat, axis=0) - flat                    # (G*k, H)
    pos = (pos_in_e * flat).sum(-1).reshape(g, k)                 # (G, k)
    keep = (pos < cap) & (top_p > 0) & (held_hot.sum(-1) > 0)
    pos = jnp.minimum(pos, cap - 1)

    # dispatch/combine tensors (G, H, C)
    disp = (held_hot.astype(xg.dtype)[..., None]
            * jax.nn.one_hot(pos, cap, dtype=xg.dtype)[..., None, :]
            * keep[..., None, None].astype(xg.dtype))             # (G,k,H,C)
    combine = disp.astype(jnp.float32) * top_p[..., None, None]
    disp = disp.sum(1)                                            # (G, H, C)
    combine = combine.sum(1)                                      # (G, H, C)

    expert_in = jnp.einsum("gec,gd->ecd", disp, xg)               # (H, C, D)
    expert_in = constrain_act(expert_in, "model", None, None)     # EP
    expert_out = _expert_ffn(params, expert_in, cfg.gated_ffn)
    expert_out = constrain_act(expert_out, "model", None, None)
    out = jnp.einsum("gec,ecd->gd", combine.astype(xg.dtype), expert_out)

    if m.num_shared_experts:
        sh = params["shared"]
        up = xg @ sh["w_up"]
        if cfg.gated_ffn:
            gate = jax.nn.silu((xg @ sh["w_gate"]).astype(jnp.float32))
            h = (gate * up.astype(jnp.float32)).astype(xg.dtype)
        else:
            h = jax.nn.gelu(up.astype(jnp.float32)).astype(xg.dtype)
        out = out + h @ sh["w_down"]
    return out, aux, keep.sum(dtype=jnp.int32)


def moe_apply(params: Params, x: jnp.ndarray, cfg
              ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out, aux_loss, held rows). Groups along the
    flattened tokens; held rows are the (token, expert) pairs that the
    held experts computed."""
    b, s, d = x.shape
    t = b * s
    gsz = min(GROUP_SIZE, t)
    ng = t // gsz
    assert t % gsz == 0, (t, gsz)
    xg = x.reshape(ng, gsz, d)

    def body(_, xi):
        return None, _route_group(params, xi, cfg)

    if ng == 1:
        out, aux, rows = _route_group(params, xg[0], cfg)
        return out.reshape(b, s, d), aux, rows
    _, (outs, auxs, rows) = jax.lax.scan(body, None, xg)
    return outs.reshape(b, s, d), auxs.mean(), rows.sum()

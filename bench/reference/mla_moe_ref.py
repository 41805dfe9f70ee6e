"""Plain reference of DeepSeek-V2 (arXiv:2405.04434), one chip's share.

The forward pass as published, in float32 at full matmul precision, from
the benchmark's own weights (``harness.mla_moe_weights``).  Token
embedding; per layer RMSNorm, multi-head latent attention in its
non-absorbed form (the query through its low-rank pair with an RMSNorm
between; the key/value latent ``c_kv`` normed and expanded per head into
keys and values; a rotary key part shared by all heads; YaRN frequencies
and temperature; causal softmax at 1/sqrt(192) x mscale^2), residual,
RMSNorm, then the layer's FFN, residual; final RMSNorm and an untied
output head.  The leading dense layers run a SwiGLU MLP.  An expert
layer routes over all its published experts: softmax scores, the best
expert of each of ``n_group`` groups ranks the groups, the top
``topk_group`` groups' experts compete for the top k, whose scores times
``routed_scaling_factor`` weight them; of those, only the experts this
chip holds add their SwiGLU output, and the shared experts add theirs.
No cache, no absorption, no kernels.  Rotary is rotate-half on the rope
dimensions (the configuration's ``assumed``).

Blocked so that it fits next to the weights: one sequence at a time, and
query rows ``Q_BLOCK`` at a time in attention; the head is applied only
where a served token was chosen.

``fp8`` computes every matmul input in float8 (e4m3) with a scale per
row of activations and per output column of weights: the control, one
precision below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.lm_ref import _mm, _rmsnorm

HI = jax.lax.Precision.HIGHEST
#: query rows a block of attention scores
Q_BLOCK = 256


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _yarn(cfg):
    """(inverse frequencies of the rope dims, cos/sin scale, softmax scale)
    from the configuration's ``rope_scaling``."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    y = cfg["rope_scaling"]
    orig, factor = y["original_max_position_embeddings"], y["factor"]

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extrapolate = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low),
                                0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inv = extra / factor * (1 - extrapolate) + extra * extrapolate
    cs = _mscale(factor, y["mscale"]) / _mscale(factor, y["mscale_all_dim"])
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    sm = qk ** -0.5 * _mscale(factor, y["mscale_all_dim"]) ** 2
    return jnp.asarray(inv, jnp.float32), cs, sm


def _rope(x, inv, cs):
    """x: (S, ..., D) at positions 0..S-1, rotate-half."""
    s, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    shape = (s,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = (cs * jnp.cos(ang)).reshape(shape), \
        (cs * jnp.sin(ang)).reshape(shape)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(x, gate, up, down, fp8):
    return _mm(jax.nn.silu(_mm(x, gate, fp8)) * _mm(x, up, fp8), down, fp8)


def _attention(x, w, cfg, yarn, fp8):
    """Non-absorbed MLA of one sequence x (S, D)."""
    s = x.shape[0]
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kl = cfg["kv_lora_rank"]
    inv, cs, scale = yarn
    cq = _rmsnorm(_mm(x, w["w_dq"], fp8), w["q_norm"], eps)
    q = _mm(cq, w["w_uq"], fp8).reshape(s, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, cs)], -1)
    dkv = _mm(x, w["w_dkv"], fp8)
    c_kv = _rmsnorm(dkv[:, :kl], w["kv_norm"], eps)
    k_rope = _rope(dkv[:, kl:], inv, cs)                         # (S, rope)
    k = jnp.concatenate(
        [_mm(c_kv, w["w_uk"], fp8).reshape(s, h, nope),
         jnp.broadcast_to(k_rope[:, None], (s, h, rope))], -1)
    v = _mm(c_kv, w["w_uv"], fp8).reshape(s, h, vd)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        sc = jnp.einsum("qhd,thd->hqt", qb, k, precision=HI) * scale
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(jnp.arange(s)[None, None] <= rows[None, :, None], sc,
                       -jnp.inf)
        return jnp.einsum("hqt,thd->qhd", jax.nn.softmax(sc, -1), v,
                          precision=HI)
    o = jax.lax.map(block, jnp.arange(s // Q_BLOCK)).reshape(s, h * vd)
    return _mm(o, w["wo"], fp8)


def _experts(x, w, cfg, fp8):
    """The held routed experts' part of an expert layer, plus the shared
    experts, for one sequence x (S, D)."""
    s = x.shape[0]
    e, k = cfg["published"]["n_routed_experts"], cfg["num_experts_per_tok"]
    groups, keep = cfg["n_group"], cfg["topk_group"]
    first = cfg["deployment"]["first_expert_held"]
    probs = jax.nn.softmax(_mm(x, w["router"], fp8), -1)         # (S, E)
    best = probs.reshape(s, groups, e // groups).max(-1)         # (S, G)
    _, top_g = jax.lax.top_k(best, keep)
    in_group = (jnp.arange(groups)[None, None] == top_g[..., None]).any(1)
    scores = jnp.where(jnp.repeat(in_group, e // groups, 1), probs, 0.0)
    top_w, top_i = jax.lax.top_k(scores, k)
    if cfg["norm_topk_prob"]:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    else:
        top_w = top_w * cfg["routed_scaling_factor"]
    out = _swiglu(x, w["s_gate"], w["s_up"], w["s_down"], fp8)
    for j in range(cfg["n_routed_experts"]):
        gate = (top_w * (top_i == first + j)).sum(-1, keepdims=True)
        out = out + gate * _swiglu(x, w["e_gate"][j], w["e_up"][j],
                                   w["e_down"][j], fp8)
    return out


def _layer(x, w, cfg, yarn, fp8, moe: bool):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rmsnorm(x, w["norm1"], eps), w, cfg, yarn, fp8)
    a = _rmsnorm(x, w["norm2"], eps)
    if moe:
        return x + _experts(a, w, cfg, fp8)
    return x + _swiglu(a, w["w_gate"], w["w_up"], w["w_down"], fp8)


def _hidden(weights, cfg, tokens, fp8):
    """One sequence's final hidden states (S, D), S a multiple of Q_BLOCK."""
    yarn = _yarn(cfg)
    x = weights["embed"][tokens].astype(jnp.float32)
    for i in range(cfg["first_k_dense_replace"]):
        p = f"dense{i}."
        w = {k[len(p):]: a for k, a in weights.items() if k.startswith(p)}
        x = _layer(x, w, cfg, yarn, fp8, moe=False)
    moe = {k[4:]: a for k, a in weights.items() if k.startswith("moe.")}
    x, _ = jax.lax.scan(lambda x, w: (_layer(x, w, cfg, yarn, fp8, True),
                                      None), x, moe)
    return x


def _per_sequence(weights, cfg, tokens, positions, fn):
    """``fn`` of each sequence's logits at its positions, one array for
    each of ``fn.precisions`` (float8 or not), and its row of
    ``fn.extra``."""
    s = tokens.shape[1]
    pad = -s % Q_BLOCK
    tokens = jnp.pad(tokens, ((0, 0), (0, pad)))
    head = weights["lm_head"].astype(jnp.float32)
    norm = weights["final_norm"].astype(jnp.float32)
    eps = cfg["rms_norm_eps"]

    def one(args):
        toks, pos, extra = args
        outs = []
        for fp8 in fn.precisions:
            x = _rmsnorm(_hidden(weights, cfg, toks, fp8)[pos], norm, eps)
            outs.append(_mm(x, head, fp8))
        return fn(*outs, extra)
    return jax.lax.map(one, (tokens, positions, fn.extra))


def served_gaps(weights, cfg, tokens, positions, served):
    """How far each served token's logit lies below the reference's best.

    tokens (B, S): prompt and served tokens, padded; positions (B, N):
    the position whose logits chose each served token; served (B, N).
    Returns (B, N) gaps, >= 0."""
    def gap(at, sv):
        return at.max(-1) - jnp.take_along_axis(at, sv[:, None], -1)[:, 0]
    gap.precisions, gap.extra = (False,), served
    return _per_sequence(weights, cfg, tokens, positions, gap)


def control_gaps(weights, cfg, tokens, positions):
    """The gap, under the reference, of the token that the float8 control
    puts first at each position."""
    def gap(at, at8, _):
        pick = at8.argmax(-1)
        return at.max(-1) - jnp.take_along_axis(at, pick[:, None], -1)[:, 0]
    gap.precisions, gap.extra = (False, True), positions
    return _per_sequence(weights, cfg, tokens, positions, gap)

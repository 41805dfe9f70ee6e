"""Plain reference of a dense decoder-only transformer (InternLM2 family).

The forward pass of arXiv:2403.17297 as published, in float32 at full
matmul precision, from the benchmark's own weights: token embedding;
per layer RMSNorm, grouped-query attention with rotary positions
(rotate-half form) under a causal mask, residual, RMSNorm, SwiGLU MLP,
residual; final RMSNorm and an untied output head.  No cache, no
batching tricks, no kernels.

``weights`` is the dict that ``harness.lm_weights.make`` builds:
``embed`` (V, D), per-layer stacks ``wq wk wv wo w_gate w_up w_down
norm1 norm2`` with a leading layer axis, ``final_norm`` (D,) and
``lm_head`` (D, V).

``fp8`` computes every matmul input in float8 (e4m3) with a scale per
row of activations and per output column of weights: the control, one
precision below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x, axis):
    """Round ``x`` to float8 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, H, D) at positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(weights, cfg, tokens, positions, fp8: bool = False):
    """(B, S) token ids -> (B, N, V) float32 logits at ``positions``
    (B, N): the head is applied only where a served token was chosen."""
    b, s = tokens.shape
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // h
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    f32 = lambda a: a.astype(jnp.float32)                       # noqa: E731
    x = f32(weights["embed"][tokens])
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, w):
        w = jax.tree.map(f32, w)
        a = _rmsnorm(x, w["norm1"], eps)
        q = _rope(_mm(a, w["wq"], fp8).reshape(b, s, h, hd), theta)
        k = _rope(_mm(a, w["wk"], fp8).reshape(b, s, kh, hd), theta)
        v = _mm(a, w["wv"], fp8).reshape(b, s, kh, hd)
        q = q.reshape(b, s, kh, h // kh, hd)
        sc = jnp.einsum("bskgd,btkd->bkgst", q, k,
                        precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(
                            jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bkgst,btkd->bskgd", p, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + _mm(o.reshape(b, s, h * hd), w["wo"], fp8)
        a = _rmsnorm(x, w["norm2"], eps)
        g = jax.nn.silu(_mm(a, w["w_gate"], fp8)) * _mm(a, w["w_up"], fp8)
        return x + _mm(g, w["w_down"], fp8), None

    keys = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "norm1",
            "norm2")
    x, _ = jax.lax.scan(layer, x, {k: weights[k] for k in keys})
    x = jnp.take_along_axis(x, positions[..., None], axis=1)
    x = _rmsnorm(x, f32(weights["final_norm"]), eps)
    return _mm(x, f32(weights["lm_head"]), fp8)


def served_gaps(weights, cfg, tokens, positions, served):
    """How far each served token's logit lies below the reference's best.

    tokens (B, S): prompt and served tokens, padded; positions (B, N):
    the position whose logits chose each served token; served (B, N).
    Returns (B, N) gaps, >= 0."""
    at = logits(weights, cfg, tokens, positions)                # (B, N, V)
    mine = jnp.take_along_axis(at, served[..., None], axis=-1)[..., 0]
    return at.max(-1) - mine


def control_gaps(weights, cfg, tokens, positions):
    """The gap, under the reference, of the token that the float8 control
    puts first at each position."""
    at = logits(weights, cfg, tokens, positions)
    at8 = logits(weights, cfg, tokens, positions, fp8=True)
    pick = at8.argmax(-1)
    return at.max(-1) - jnp.take_along_axis(at, pick[..., None], -1)[..., 0]

"""Primitive layers: norms, embeddings, rope, FFNs.

Everything is a pure function over a params pytree (nested dicts of
jnp arrays).  Initializers take an explicit PRNG key and return params in the
config's dtype (master/compute dtype policies live in train/).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.context import BATCH, constrain_act

Params = Dict[str, Any]


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), dtype=jnp.float32)
            * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature for a context stretched ``factor``
    times (DeepSeek-V2's ``yarn_get_mscale``)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(dim: int, theta: float, y) -> np.ndarray:
    """DeepSeek-V2's YaRN inverse frequencies (``y``: a YarnConfig):
    dimensions that turn fewer than ``beta_slow`` times over the original
    context are interpolated (divided by ``factor``), those that turn
    more than ``beta_fast`` times are kept, with a linear ramp between."""
    def correction_dim(rotations):
        return (dim * math.log(y.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(y.beta_fast)), 0)
    high = min(math.ceil(correction_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    keep = 1.0 - ramp
    return (extra / y.factor * (1.0 - keep) + extra * keep).astype(np.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               scaling=None) -> jnp.ndarray:
    """x: (..., S, H, D); positions: broadcastable to (..., S).
    ``scaling``: a YarnConfig, whose frequencies and cos/sin scale apply."""
    if theta <= 0:  # archs without rope (whisper)
        return x
    d = x.shape[-1]
    freqs = (rope_frequencies(d, theta) if scaling is None
             else jnp.asarray(yarn_frequencies(d, theta, scaling)))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]                           # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, d: int) -> jnp.ndarray:
    """Whisper-style sinusoidal embeddings (no rope archs)."""
    pos = np.arange(seq_len)[:, None]
    dim = np.arange(d // 2)[None, :]
    inv = 1.0 / (10_000 ** (dim / max(d // 2 - 1, 1)))
    ang = pos * inv
    return jnp.asarray(
        np.concatenate([np.sin(ang), np.cos(ang)], axis=-1), dtype=jnp.float32)


# ---------------------------------------------------------------------------
# FFNs
# ---------------------------------------------------------------------------
def ffn_init(key, d_model: int, d_ff: int, dtype, gated: bool) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d_model, d_ff, dtype),
         "w_down": dense_init(ks[1], d_ff, d_model, dtype)}
    if gated:
        p["w_gate"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


def ffn_apply(params: Params, x: jnp.ndarray, gated: bool) -> jnp.ndarray:
    up = constrain_act(x @ params["w_up"], BATCH, None, "model")
    if gated:
        gate = jax.nn.silu((x @ params["w_gate"]).astype(jnp.float32))
        h = (gate * up.astype(jnp.float32)).astype(x.dtype)
    else:
        h = jax.nn.gelu(up.astype(jnp.float32)).astype(x.dtype)
    return h @ params["w_down"]


def relu_sq_ffn_init(key, d_model: int, d_ff: int, dtype) -> Params:
    """RWKV channel-mix: relu(x W_k)^2 W_v with token-shift mixing."""
    ks = jax.random.split(key, 3)
    return {"w_k": dense_init(ks[0], d_model, d_ff, dtype),
            "w_v": dense_init(ks[1], d_ff, d_model, dtype),
            "mix_k": jnp.full((d_model,), 0.5, dtype=dtype)}


def relu_sq_ffn_apply(params: Params, x: jnp.ndarray,
                      x_prev: jnp.ndarray) -> jnp.ndarray:
    mix = params["mix_k"].astype(jnp.float32)
    xk = (x.astype(jnp.float32) * mix
          + x_prev.astype(jnp.float32) * (1 - mix)).astype(x.dtype)
    h = jnp.square(jax.nn.relu((xk @ params["w_k"]).astype(jnp.float32)))
    return h.astype(x.dtype) @ params["w_v"]

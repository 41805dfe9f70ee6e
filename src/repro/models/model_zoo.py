"""Top-level model API: init / train forward / prefill / decode.

Entry points used by train/, serving/ and launch/:

  init_params(cfg, key)                      -> params pytree
  forward_train(params, cfg, batch)          -> (logits, aux_loss)
  init_decode_state(cfg, batch, max_len, kv_mode, page_size) -> state
  decode_step(params, cfg, state, tokens, kv_mode) -> (logits, state)
  prefill(params, cfg, tokens, ...)          -> (logits, state)

KV modes: "dense" | "paged_flat" (NDPage) | "paged_radix" (2-level baseline).
For a stack with expert layers, a decode state's ``moe_rows_held`` is the
last step's count of (token, expert) pairs computed by the held experts.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import config as C
from repro.core import block_table as BT
from repro.models import transformer as T
from repro.models.layers import (dtype_of, embed_init, dense_init, rmsnorm,
                                 rmsnorm_init, sinusoidal_positions)

Params = Dict[str, Any]

DEFAULT_PAGE_SIZE = 64


def build_model(name_or_cfg) -> C.ArchConfig:
    if isinstance(name_or_cfg, C.ArchConfig):
        return name_or_cfg
    return C.get_arch(name_or_cfg)


def _encoder_cfg(cfg: C.ArchConfig) -> C.ArchConfig:
    return dataclasses.replace(
        cfg, num_layers=cfg.encoder_layers,
        layer_pattern=((C.ATTN, C.DENSE_FF),), prefix_pattern=(),
        encoder_layers=0)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(cfg: C.ArchConfig, key) -> Params:
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    params: Params = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dt),
        "stack": T.stack_init(ks[1], cfg, cross=cfg.is_encdec),
        "final_norm": rmsnorm_init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[2], cfg.d_model, cfg.vocab_size, dt)
    if cfg.is_encdec:
        ecfg = _encoder_cfg(cfg)
        params["encoder"] = T.stack_init(ks[3], ecfg, cross=False)
        params["enc_norm"] = rmsnorm_init(cfg.d_model, dt)
    return params


def _logits(params: Params, cfg, x: jnp.ndarray) -> jnp.ndarray:
    x = rmsnorm(params["final_norm"], x, cfg.rms_norm_eps)
    if cfg.tie_embeddings:
        return (x @ params["embed"].T).astype(jnp.float32)
    return (x @ params["lm_head"]).astype(jnp.float32)


def _encode(params: Params, cfg, audio_frames: jnp.ndarray) -> jnp.ndarray:
    """Stub-frontend encoder: frames are precomputed embeddings (B, Se, D)."""
    ecfg = _encoder_cfg(cfg)
    se = audio_frames.shape[1]
    pos = sinusoidal_positions(se, cfg.d_model).astype(audio_frames.dtype)
    x = audio_frames + pos[None]
    x, _ = T.stack_apply_train(params["encoder"], x,
                               jnp.arange(se)[None], ecfg, causal=False)
    return rmsnorm(params["enc_norm"], x, cfg.rms_norm_eps)


# ---------------------------------------------------------------------------
# train forward
# ---------------------------------------------------------------------------
def forward_train(params: Params, cfg: C.ArchConfig, batch: Dict[str, Any]):
    """batch: tokens (B, S_tok) [+ audio_frames / vision_embeds stubs].

    Returns (logits (B, S, V) f32, aux_loss scalar).
    """
    tokens = batch["tokens"]
    x = params["embed"][tokens]
    if cfg.vision_tokens:
        vis = batch["vision_embeds"].astype(x.dtype)  # (B, Tv, D)
        x = jnp.concatenate([vis, x], axis=1)
    b, s, _ = x.shape
    positions = jnp.arange(s)[None]
    if cfg.rope_theta <= 0:  # sinusoidal-position archs (whisper)
        x = x + sinusoidal_positions(s, cfg.d_model).astype(x.dtype)[None]

    enc_out = None
    if cfg.is_encdec:
        enc_out = _encode(params, cfg, batch["audio_frames"])

    x, aux = T.stack_apply_train(params["stack"], x, positions, cfg,
                                 enc_out=enc_out, causal=True)
    return _logits(params, cfg, x), aux


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def init_decode_state(cfg: C.ArchConfig, batch: int, max_len: int,
                      kv_mode: str = "dense",
                      page_size: int = DEFAULT_PAGE_SIZE,
                      table=None, num_pages: int | None = None
                      ) -> Dict[str, Any]:
    """Concrete zero-initialized decode state.

    For paged modes the default table is the identity pre-mapped layout
    (page p of seq b -> physical b*max_pages+p); the serving engine replaces
    it with KVPageManager-built tables.  ``num_pages`` sizes the physical
    KV pools (default ``batch * max_pages``); callers with a host-side
    page allocator MUST pass their pool size — a physical page id at or
    past the pool silently corrupts KV through clamped scatter/gather.
    """
    max_pages = -(-max_len // page_size)
    padded_len = max_pages * page_size
    pages_per_layer = (batch * max_pages if num_pages is None
                       else num_pages)
    state: Dict[str, Any] = {
        "lengths": jnp.zeros((batch,), jnp.int32),
        "stack": T.stack_init_state(cfg, batch, padded_len, kv_mode,
                                    page_size, pages_per_layer),
    }
    if kv_mode != "dense":
        if table is None:
            flat = jnp.arange(batch * max_pages, dtype=jnp.int32
                              ).reshape(batch, max_pages)
            table = (flat if kv_mode == BT.FLAT
                     else BT.radix_from_flat(
                         flat, leaf_size=min(16, max_pages)))
        state["table"] = table
    if cfg.is_encdec:
        state["enc_out"] = jnp.zeros(
            (batch, cfg.encoder_seq_len, cfg.d_model), dtype_of(cfg))
    if T.has_moe(cfg):
        # the last step's (token, expert) pairs computed by held experts
        state["moe_rows_held"] = jnp.zeros((), jnp.int32)
    return state


@functools.partial(jax.jit, static_argnames=("cfg", "kv_mode"),
                   donate_argnames=("state",))
def decode_step(params: Params, cfg: C.ArchConfig, state: Dict[str, Any],
                tokens: jnp.ndarray, kv_mode: str = "dense"):
    """One decode step. tokens: (B,) int32. Returns (logits (B, V), state).

    The state's buffers are donated to the new state's, so the caches are
    appended in place: the caller keeps only the returned state."""
    lengths = state["lengths"]
    x = params["embed"][tokens][:, None, :]
    if cfg.rope_theta <= 0:
        # sinusoidal position embedding of the current index, per sequence
        d = cfg.d_model
        half = d // 2
        inv = 1.0 / (10_000 ** (jnp.arange(half) / max(half - 1, 1)))
        ang = lengths[:, None].astype(jnp.float32) * inv[None]
        pos_emb = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
        x = x + pos_emb[:, None, :].astype(x.dtype)

    table = state.get("table")
    x, new_stack, rows = T.stack_apply_decode(
        params["stack"], state["stack"], x, lengths, cfg,
        kv_mode=kv_mode, table=table, enc_out=state.get("enc_out"))
    logits = _logits(params, cfg, x)[:, 0]
    new_state = dict(state)
    new_state["stack"] = new_stack
    new_state["lengths"] = lengths + 1
    if rows is not None:
        new_state["moe_rows_held"] = rows
    return logits, new_state


def prefill(params: Params, cfg: C.ArchConfig, tokens: jnp.ndarray,
            kv_mode: str = "dense", max_len: Optional[int] = None,
            page_size: int = DEFAULT_PAGE_SIZE, state=None,
            audio_frames=None):
    """Sequential prefill via decode_step scan (exercises the paged append
    path exactly as decode does).  tokens: (B, S_prompt)."""
    b, sp = tokens.shape
    max_len = max_len or (sp + 128)
    if state is None:
        state = init_decode_state(cfg, b, max_len, kv_mode, page_size)
    if cfg.is_encdec:
        assert audio_frames is not None
        state = dict(state)
        state["enc_out"] = _encode(params, cfg, audio_frames)

    def step(st, tok):
        logits, st = decode_step(params, cfg, st, tok, kv_mode)
        return st, logits

    state, logits_seq = jax.lax.scan(step, state, tokens.T)
    return logits_seq[-1], state

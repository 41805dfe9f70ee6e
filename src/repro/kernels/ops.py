"""jit'd public wrappers for the Pallas kernels and their jnp references.

Dispatch policy:
  * on TPU: compiled Pallas kernels (paged attention: where a page's
    (KH, D) lie on whole (8, 128) tiles, else the jnp reference)
  * REPRO_KERNEL_IMPL=interpret: Pallas in interpret mode (CPU validation)
  * otherwise (CPU): the jnp reference oracles
A failure to initialize the device propagates; it never selects the CPU.

so models/ and serving/ call one API regardless of backend.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import pages_tile, paged_attention_pallas
from repro.kernels.paged_latent_attention import \
    paged_latent_attention_pallas


def _impl(override: Optional[str]) -> str:
    if override:
        return override
    env = os.environ.get("REPRO_KERNEL_IMPL")
    if env:
        return env
    return "pallas" if jax.devices()[0].platform == "tpu" else "ref"


def paged_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                    v_pages: jnp.ndarray, block_table: jnp.ndarray,
                    valid_lens: jnp.ndarray, layer=0, *, window: int = 0,
                    impl: Optional[str] = None) -> jnp.ndarray:
    """Decode attention over paged KV pools, (N, page, K, D) or stacked
    (L, N, page, K, D) and read at ``layer`` (see
    kernels/paged_attention.py)."""
    which = _impl(impl)
    if which == "ref" or (which == "pallas" and not pages_tile(k_pages)):
        return ref.paged_attention_ref(q, k_pages, v_pages, block_table,
                                       valid_lens, layer, window=window)
    return paged_attention_pallas(q, k_pages, v_pages, block_table,
                                  valid_lens, layer, window=window,
                                  interpret=(which == "interpret"))


def paged_latent_attention(q: jnp.ndarray, pool: jnp.ndarray,
                           block_table: jnp.ndarray, valid_lens: jnp.ndarray,
                           layer=0, *, kv_lora: int, scale: float,
                           impl: Optional[str] = None) -> jnp.ndarray:
    """Absorbed MLA decode over a paged latent pool, (N, page, R) or
    stacked (L, N, page, R) and read at ``layer`` (see
    kernels/paged_latent_attention.py)."""
    which = _impl(impl)
    if which == "ref":
        return ref.paged_latent_attention_ref(q, pool, block_table,
                                              valid_lens, layer,
                                              kv_lora=kv_lora, scale=scale)
    return paged_latent_attention_pallas(q, pool, block_table, valid_lens,
                                         layer, kv_lora=kv_lora, scale=scale,
                                         interpret=(which == "interpret"))


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    *, causal: bool = True, window: int = 0,
                    impl: Optional[str] = None) -> jnp.ndarray:
    """Blockwise attention (see kernels/flash_attention.py)."""
    which = _impl(impl)
    if which == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  interpret=(which == "interpret"))

"""Random weights of a DeepSeek-V2 configuration, made from a seed.

The benchmark owns the weights: ``make`` builds them on the device, one
jitted call an array, in the dtype they are served in (the router in
float32, as the published gate computes), and both the program under
test (through ``program_tree``) and the plain reference read the same
arrays.  Scales keep activations of order one through the depth:
projections draw N(0, 1/fan_in), the embedding N(0, 0.02^2), and every
norm scale 1 + N(0, 0.1^2) so that a path that skips a norm shows.

Names: ``embed``, ``lm_head``, ``final_norm``; ``dense<i>.<w>`` for each
leading dense layer i (no layer axis); ``moe.<w>`` stacked over the
expert layers.  A layer's latent attention is ``w_dq q_norm w_uq w_dkv
kv_norm w_uk w_uv wo`` and ``norm1 norm2``; a dense FFN ``w_gate w_up
w_down``; an expert layer ``router`` (D, E), the held experts ``e_gate
e_up e_down`` (held, ...) and the shared experts ``s_gate s_up s_down``.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from harness.lm_weights import seed_key

ATTN = ("w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_uk", "w_uv", "wo",
        "norm1", "norm2")


def _attn_shapes(cfg: Dict) -> Dict[str, tuple]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return {"w_dq": (d, ql), "q_norm": (ql,), "w_uq": (ql, h * (nope + rope)),
            "w_dkv": (d, kl + rope), "kv_norm": (kl,),
            "w_uk": (kl, h * nope), "w_uv": (kl, h * vd), "wo": (h * vd, d),
            "norm1": (d,), "norm2": (d,)}


def shapes(cfg: Dict) -> Dict[str, tuple]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    held = cfg["n_routed_experts"]
    dense = cfg["first_k_dense_replace"]
    n = cfg["num_hidden_layers"] - dense
    attn = _attn_shapes(cfg)
    out = {"embed": (v, d), "lm_head": (d, v), "final_norm": (d,)}
    for i in range(dense):
        out.update({f"dense{i}.{k}": s for k, s in attn.items()})
        out.update({f"dense{i}.w_gate": (d, f), f"dense{i}.w_up": (d, f),
                    f"dense{i}.w_down": (f, d)})
    moe = dict(attn, router=(d, cfg["published"]["n_routed_experts"]),
               e_gate=(held, d, fe), e_up=(held, d, fe),
               e_down=(held, fe, d), s_gate=(d, fs), s_up=(d, fs),
               s_down=(fs, d))
    out.update({f"moe.{k}": (n,) + s for k, s in moe.items()})
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, kind: str, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "embed":
        w = 0.02 * z
    elif kind == "norm":
        w = 1.0 + 0.1 * z
    else:
        w = z / np.sqrt(shape[-2])
    return w.astype(dtype)


def make(cfg: Dict, seed: int, dtype="bfloat16") -> Dict[str, jax.Array]:
    dtype = jnp.dtype(dtype)
    shp = shapes(cfg)
    names = sorted(shp)
    keys = jax.random.split(seed_key(seed), len(names))
    out = {}
    for name, k in zip(names, keys):
        base = name.split(".")[-1]
        kind = ("embed" if name == "embed" else
                "norm" if base.endswith("norm") or base.startswith("norm")
                else "matrix")
        out[name] = _draw(k, shp[name], kind,
                          jnp.dtype(jnp.float32) if base == "router"
                          else dtype)
    return out


def program_tree(w: Dict[str, jax.Array], cfg: Dict) -> Dict:
    """The same arrays in the parameter tree of ``repro.models``."""
    def attn(p):
        return {"norm1": {"scale": w[p + "norm1"]},
                "norm2": {"scale": w[p + "norm2"]},
                "mixer": {"w_dq": w[p + "w_dq"],
                          "q_norm": {"scale": w[p + "q_norm"]},
                          "w_uq": w[p + "w_uq"], "w_dkv": w[p + "w_dkv"],
                          "kv_norm": {"scale": w[p + "kv_norm"]},
                          "w_uk": w[p + "w_uk"], "w_uv": w[p + "w_uv"],
                          "wo": w[p + "wo"]}}

    prefix = []
    for i in range(cfg["first_k_dense_replace"]):
        p = f"dense{i}."
        prefix.append(dict(attn(p), ffn={k: w[p + k] for k in
                                         ("w_gate", "w_up", "w_down")}))
    moe = dict(attn("moe."), ffn={
        "router": w["moe.router"], "w_gate": w["moe.e_gate"],
        "w_up": w["moe.e_up"], "w_down": w["moe.e_down"],
        "shared": {"w_gate": w["moe.s_gate"], "w_up": w["moe.s_up"],
                   "w_down": w["moe.s_down"]}})
    return {"embed": w["embed"], "lm_head": w["lm_head"],
            "final_norm": {"scale": w["final_norm"]},
            "stack": {"prefix": prefix, "scan": {"block_0": moe}}}

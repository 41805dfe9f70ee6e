"""Random weights of a dense transformer configuration, made from a seed.

The benchmark owns the weights: ``make`` builds them on the device in one
jitted call, in the dtype they are served in, and both the program under
test (through ``program_tree``) and the plain reference read the same
arrays.  Scales keep activations of order one through the depth:
projections draw N(0, 1/fan_in), the embedding N(0, 0.02^2), and every
norm scale 1 + N(0, 0.1^2) so that a path that skips a norm shows.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
NORMS = ("norm1", "norm2")


def shapes(cfg: Dict) -> Dict[str, tuple]:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    n = cfg["num_hidden_layers"]
    return {"embed": (v, d), "lm_head": (d, v), "final_norm": (d,),
            "wq": (n, d, h * hd), "wk": (n, d, kh * hd), "wv": (n, d, kh * hd),
            "wo": (n, h * hd, d), "w_gate": (n, d, f), "w_up": (n, d, f),
            "w_down": (n, f, d), "norm1": (n, d), "norm2": (n, d)}


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64 bits and more included."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def make(cfg: Dict, seed: int, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    shp = shapes(cfg)
    names = sorted(shp)

    @jax.jit
    def build(key):
        out = {}
        for name, k in zip(names, jax.random.split(key, len(names))):
            z = jax.random.normal(k, shp[name], jnp.float32)
            if name == "embed":
                w = 0.02 * z
            elif name in NORMS or name == "final_norm":
                w = 1.0 + 0.1 * z
            else:
                w = z / np.sqrt(shp[name][-2])
            out[name] = w.astype(dtype)
        return out

    return build(seed_key(seed))


def program_tree(w: Dict[str, jax.Array]) -> Dict:
    """The same arrays in the parameter tree of ``repro.models``."""
    block = {"norm1": {"scale": w["norm1"]}, "norm2": {"scale": w["norm2"]},
             "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
             "ffn": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}
    return {"embed": w["embed"], "lm_head": w["lm_head"],
            "final_norm": {"scale": w["final_norm"]},
            "stack": {"prefix": [], "scan": {"block_0": block}}}

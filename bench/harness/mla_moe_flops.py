"""Operations and bytes of DeepSeek-V2's decode, counted from shapes.

``decode_token_flops`` is one token of the served stack at a given
context, as the program computes it with ``W_uk`` and ``W_uv`` absorbed:
every projection of latent attention, its scores and weighted sum over
the context in the latent space, the dense FFN of the leading dense
layers, the shared experts and the router of each expert layer, and the
output head (2 FLOPs per multiply-add; norms, softmax and rotary are
left out as they are O(D) per token).  The routed experts are counted
apart, from the (token, expert) pairs the held experts computed:
``routed_flops``.  ``mla_kernel_cost`` is one call of the paged
latent-attention kernel for a batch: QK over each sequence's live rows
and PV over their ``c_kv`` lanes, and the bytes of q, the live latent
rows and the output, a row counted at kv_lora + rope lanes whatever
padding the pool's layout adds.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple


def _row(cfg: Dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def decode_token_flops(cfg: Dict, context: int) -> float:
    """FLOPs of one token that attends to ``context`` tokens (itself
    included), routed experts left out."""
    d, h, v = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["vocab_size"]
    lora, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    qk = nope + cfg["qk_rope_head_dim"]
    vd = cfg["v_head_dim"]
    proj = 2 * (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * h * qk
                + d * _row(cfg) + h * nope * lora + h * lora * vd
                + h * vd * d)
    attn = 2 * h * (_row(cfg) + lora) * context
    dense = cfg["first_k_dense_replace"]
    moe = cfg["num_hidden_layers"] - dense
    ffn = (dense * 3 * 2 * d * cfg["intermediate_size"]
           + moe * (3 * 2 * d * cfg["moe_intermediate_size"]
                    * cfg["n_shared_experts"]
                    + 2 * d * cfg["published"]["n_routed_experts"]))
    return float(cfg["num_hidden_layers"] * (proj + attn) + ffn + 2 * d * v)


def routed_flops(cfg: Dict, rows: int) -> float:
    """FLOPs of ``rows`` (token, expert) pairs through a routed expert."""
    return float(rows * 3 * 2 * cfg["hidden_size"]
                 * cfg["moe_intermediate_size"])


def mla_kernel_cost(cfg: Dict, contexts: Iterable[int],
                    dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one kernel call over sequences of these lengths."""
    h, lora, row = cfg["num_attention_heads"], cfg["kv_lora_rank"], _row(cfg)
    flops = 0.0
    nbytes = 0.0
    for n in contexts:
        flops += 2 * h * (row + lora) * n
        nbytes += dtype_bytes * (h * row + n * row + h * lora)
    return flops, nbytes

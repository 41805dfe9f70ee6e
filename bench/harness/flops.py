"""Operations and bytes the algorithms need, counted from shapes.

``decode_token_flops`` is one token of a dense decoder at a given context:
every projection and MLP matmul, attention's QK and PV over the context,
and the output head (2 FLOPs per multiply-add; norms, softmax and rotary
are left out as they are O(D) per token).  ``paged_attn_cost`` is one
call of the paged decode-attention kernel for a batch: the FLOPs of QK
and PV over each sequence's live tokens, and the bytes of q, the live K
and V rows and the output.  Dead pages, padding and the grid's layout do
not count: a kernel that skips them is read against the same work.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple


def _dims(cfg: Dict):
    d = cfg["hidden_size"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d, h, kh, d // h


def decode_token_flops(cfg: Dict, context: int) -> float:
    """FLOPs of one token that attends to ``context`` tokens (itself
    included)."""
    d, h, kh, hd = _dims(cfg)
    f, v, n = cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]
    proj = 2 * d * (h * hd) * 2 + 2 * d * (kh * hd) * 2     # q, o; k, v
    mlp = 3 * 2 * d * f
    attn = 2 * 2 * h * hd * context
    return float(n * (proj + mlp + attn) + 2 * d * v)


def paged_attn_cost(cfg: Dict, contexts: Iterable[int],
                    dtype_bytes: int = 2) -> Tuple[float, float]:
    """(FLOPs, bytes) of one kernel call over sequences of these lengths."""
    _, h, kh, hd = _dims(cfg)
    flops = 0.0
    nbytes = 0.0
    for n in contexts:
        flops += 2 * 2 * h * hd * n
        nbytes += dtype_bytes * (2 * h * hd + 2 * n * kh * hd)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: Dict) -> float:
    """The roofline bound: the larger of compute time and memory time."""
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])

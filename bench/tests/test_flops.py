"""Operation and byte counts, against hand counts at a small shape."""
import pytest

from harness import flops

CFG = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 4,
       "num_key_value_heads": 2, "num_hidden_layers": 3, "vocab_size": 10}


def test_decode_token_flops_by_hand():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8 -> 192 MACs; MLP 3 x 8x16 =
    # 384 MACs; attention over 5 tokens: 4 heads x 2 dims x 5 x 2 = 80 MACs
    per_layer = 2 * (192 + 384 + 80)
    head = 2 * 8 * 10
    assert flops.decode_token_flops(CFG, 5) == 3 * per_layer + head


def test_paged_attn_cost_by_hand():
    f, b = flops.paged_attn_cost(CFG, [3, 5], dtype_bytes=2)
    # QK and PV: 2 FLOPs x 2 products x 4 heads x 2 dims per token
    assert f == 2 * 2 * 4 * 2 * (3 + 5)
    # q and out: 4 heads x 2 dims each; K and V: 2 heads x 2 dims per token
    assert b == 2 * ((2 * 8 + 2 * 3 * 4) + (2 * 8 + 2 * 5 * 4))


def test_least_time_takes_the_binding_bound():
    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time(1000.0, 10.0, peaks) == pytest.approx(10.0)
    assert flops.least_time(10.0, 1000.0, peaks) == pytest.approx(100.0)

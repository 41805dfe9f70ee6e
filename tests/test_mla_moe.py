"""DeepSeek-V2's serving path on the CPU at a small size: latent pages
through the flat and radix tables, the paged latent-attention kernel, and
an expert layer that holds a share of the routed experts.

The engine is compared with the benchmark's plain float32 reference
(``bench/reference/mla_moe_ref.py``) on the benchmark's seeded weights;
logits are compared, not tokens.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import mla_moe_weights  # noqa: E402
from reference import mla_moe_ref  # noqa: E402
from systems.mla_moe_serve import arch_config  # noqa: E402

from repro.core import block_table as BT  # noqa: E402
from repro.kernels import ref as KREF  # noqa: E402
from repro.kernels.paged_latent_attention import \
    paged_latent_attention_pallas  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models.layers import ffn_apply  # noqa: E402

#: hidden 64, 4 heads, a query low-rank pair, YaRN; 16 routed experts in
#: 4 groups, of which this chip holds 4 (experts 4-7), and 2 shared
SMALL = {
    "name": "small-dsv2", "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": False, "scoring_func": "softmax",
    "topk_method": "group_limited_greedy", "moe_layer_freq": 1,
    "first_k_dense_replace": 1, "num_hidden_layers": 3,
    "hidden_size": 64, "intermediate_size": 128, "vocab_size": 257,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16,
    "moe_intermediate_size": 32, "n_shared_experts": 2,
    "n_routed_experts": 4, "num_experts_per_tok": 3,
    "n_group": 4, "topk_group": 2, "norm_topk_prob": False,
    "routed_scaling_factor": 4.0, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "beta_fast": 32, "beta_slow": 1},
    "torch_dtype": "float32",
    "published": {"num_hidden_layers": 60, "n_routed_experts": 16},
    "deployment": {"first_expert_held": 4},
}


def _reference_logits(w, cfg, seq, positions):
    """(N, V) reference logits of ``seq`` at ``positions``."""
    toks = jnp.asarray(seq, jnp.int32)[None]
    pos = jnp.asarray(positions, jnp.int32)[None]

    def logits(at, _):
        return at
    logits.precisions, logits.extra = (False,), pos
    with jax.default_matmul_precision("highest"):
        return np.asarray(mla_moe_ref._per_sequence(w, cfg, toks, pos,
                                                    logits))[0]


@pytest.mark.parametrize("table_mode", [BT.FLAT, BT.RADIX])
def test_engine_matches_reference(table_mode, monkeypatch):
    """Requests served through ServeEngine with the latent cache in paged
    pools, translated by the flat or the radix table: the logits of every
    generated token match the reference's full forward at its position.
    Tolerance 2e-4: float32 throughout, the program absorbs W_uk/W_uv and
    sums in another order than the reference (readings ~3e-6)."""
    from repro.launch import serve
    from repro.serving import Request, _engine
    arch = arch_config(SMALL)
    w = mla_moe_weights.make(SMALL, 11, "float32")
    seen = {}                                 # (req, position) -> logits
    real = _engine.decode_step

    def spy(params, cfg, state, tokens, kv_mode="dense"):
        lengths = np.asarray(state["lengths"])   # the state is donated
        logits, new = real(params, cfg, state, tokens, kv_mode=kv_mode)
        for sid in eng.sched.active_seqs():
            slot = eng.sched.slot_of[sid]
            seen[(sid, int(lengths[slot]))] = np.asarray(logits[slot])
        return logits, new
    monkeypatch.setattr(_engine, "decode_step", spy)
    rng = np.random.default_rng(3)
    reqs = [Request.build(i, rng.integers(1, SMALL["vocab_size"], 3 + 2 * i),
                          max_new_tokens=5 + i) for i in range(6)]
    eng = serve.start(arch, mla_moe_weights.program_tree(w, SMALL),
                      table_mode, reqs, max_batch=4, max_len=48, page_size=4)
    done = eng.run()
    assert len(done) == 6
    widest = 0.0
    for r in done:
        p, g = len(r.prompt), len(r.generated)
        seq = np.concatenate([r.prompt, r.generated[:-1]])
        positions = np.arange(p - 1, p - 1 + g)
        want = _reference_logits(w, SMALL, seq, positions)
        got = np.stack([seen[(r.req_id, int(i))] for i in positions])
        widest = max(widest, float(np.abs(got - want).max()))
    assert widest < 2e-4, widest


def test_expert_shares_add_up_to_the_whole_layer():
    """Four chips' shares of a 16-expert layer (4 experts each): their
    outputs, with the shared experts counted once, equal the uncut layer.
    Tolerance 1e-5: float32 sums of the same products in another order."""
    cfg = dataclasses.replace(arch_config(SMALL), dtype="float32")
    whole = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, first_expert=0, experts_held=0))
    params = MOE.moe_init(jax.random.PRNGKey(0), whole, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    full, _, rows_full = MOE.moe_apply(params, x, whole)
    only_shared = ffn_apply(params["shared"], x, gated=True)
    total, rows = -3 * only_shared, 0
    for first in range(0, 16, 4):
        part = {k: (v[first:first + 4] if k in ("w_up", "w_gate", "w_down")
                    else v) for k, v in params.items()}
        share = dataclasses.replace(whole, moe=dataclasses.replace(
            whole.moe, first_expert=first, experts_held=4))
        out, _, r = MOE.moe_apply(part, x, share)
        total, rows = total + out, rows + int(r)
    np.testing.assert_allclose(np.asarray(total), np.asarray(full),
                               atol=1e-5, rtol=1e-5)
    assert rows == int(rows_full) == 2 * 8 * cfg.moe.num_experts_per_tok


def test_group_limited_routing_follows_the_published_rule():
    """Softmax scores; the top 2 of 4 groups by their best expert; top 3
    of those groups' experts; weights the scores times 4, not
    renormalised."""
    m = arch_config(SMALL)
    probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(2),
                                             (32, 16)), -1)
    w, i = MOE.route(probs, m.moe)
    p = np.asarray(probs)
    for t in range(32):
        groups = np.argsort(-p[t].reshape(4, 4).max(1))[:2]
        allowed = np.concatenate([np.arange(4 * g, 4 * g + 4)
                                  for g in groups])
        best = allowed[np.argsort(-p[t][allowed])[:3]]
        assert set(np.asarray(i[t]).tolist()) == set(best.tolist())
        np.testing.assert_allclose(np.sort(np.asarray(w[t])),
                                   np.sort(4 * p[t][best]), rtol=1e-6)


@pytest.mark.parametrize("lens,layers", [((1, 75, 128, 0), 0),
                                         ((40, 5, 100, 128), 3)])
def test_latent_kernel_matches_reference(lens, layers):
    """The Pallas kernel in interpret mode against its jnp reference:
    several blocks a sequence, partial last pages, unmapped pages inside
    the live range, an empty sequence; a pool of its own, or one layer's
    of a stack read in place by index.  Tolerance 1e-5: float32, the
    online softmax sums blocks in another order."""
    rng = np.random.default_rng(sum(lens))
    b, h, r, kvl, page, maxp = 4, 8, 40, 32, 4, 32     # 16 pages a block
    n = b * maxp + 3
    shape = ((layers,) if layers else ()) + (n, page, r)
    pool = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    layer = layers - 2 if layers else 0
    table = rng.permutation(n)[:b * maxp].reshape(b, maxp).astype(np.int32)
    table[1, 1] = -1
    table[2, 7] = -1
    table[2, 20] = -1
    table[3, 12:17] = -1
    q = jnp.asarray(rng.standard_normal((b, h, r)), jnp.float32)
    lens = jnp.asarray(lens, jnp.int32)
    got = paged_latent_attention_pallas(q, pool, jnp.asarray(table), lens,
                                        layer, kv_lora=kvl, scale=0.2,
                                        interpret=True)
    want = KREF.paged_latent_attention_ref(
        q, pool[layer] if layers else pool, jnp.asarray(table), lens,
        kv_lora=kvl, scale=0.2)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want)[live], atol=1e-5, rtol=1e-5)
    assert np.all(np.asarray(got)[~live] == 0)


def test_yarn_scales_of_the_published_config():
    """DeepSeek-V2's YaRN: mscale(40, 0.707)^2 on 1/sqrt(192), and cos/sin
    unscaled (mscale equals mscale_all_dim)."""
    from repro.config import get_arch
    from repro.models import attention as A
    from repro.models.layers import yarn_frequencies
    cfg = get_arch("deepseek-v2-236b")
    m = 0.1 * 0.707 * np.log(40) + 1
    assert A.mla_softmax_scale(cfg) == pytest.approx(m * m / np.sqrt(192))
    f = yarn_frequencies(64, 1e4, cfg.rope_scaling)
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    # the fastest dims keep their frequency, the slowest are divided by 40
    np.testing.assert_allclose(f[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(f[23:], base[23:] / 40, rtol=1e-6)
    assert A.latent_row(cfg) == 640

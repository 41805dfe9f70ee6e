"""Paged-KV serving (``repro.serving.ServeEngine``) of DeepSeek-V2: latent
attention whose one-row-a-token cache lives in the paged pool, and an
expert layer that holds this chip's share of the routed experts.

Set-up makes the weights from the seed on the device, builds the engine
the configuration describes (``repro.launch.serve.start``) and serves one
one-token request through it, which compiles the decode step.  The
window drives the engine one step at a time (``run(max_steps=1)``) under
the mix's closed loop, as ``lm_serve`` does: each client sends its next
request in the step after its last one finished, and a token's time is
the end of the engine step that produced it.  A traced run records the
program's spans and counters (``repro.util.spans``) through the whole
window, traces the steps of a slice of a few seconds from its middle,
and keeps the compiled decode step's text, by which the device trace's
ops are given their named scopes (``mla``, ``moe``).

``correct``: once the window has closed and the engine is freed, a sample
of the requests that finished, drawn from the seed with the longest of
them in it, is run through the plain reference
(``bench/reference/mla_moe_ref.py``) over prompt and served tokens; the
gaps by which served tokens' logits lie below the reference's best are
compared with their limits: their 95th percentile, and how many exceed
``FAR`` logits.  Under bfloat16 a token's top-6 experts now and then
differ from float32's where two scores nearly tie, and with weights
scaled by 16 that moves a few logits far, as far as float8 moves them:
the widest gap separates nothing, the percentile separates the
precisions, and the count catches a few tokens served wrong.  Every
request that finished must have served its full output.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from harness import chat, mla_moe_weights
from systems.lm_serve import Sent, sample

#: limits of the gaps (logits) of served tokens below the reference's
#: best: their 95th percentile, and the count of gaps over FAR; set
#: between the program's readings on a v5e and the float8 control's
#: (PERF.md section 2)
GAP_P95_LIMIT = 0.25
FAR = 2.0
FAR_LIMIT = 4
LIMITS = {"served_gap_p95": GAP_P95_LIMIT, "served_gaps_over_2": FAR_LIMIT}
#: seconds of the window that the traced run traces, from its middle
TRACE_SECONDS = 3.0


def arch_config(cfg: Dict):
    """The program's ArchConfig with the configuration's numbers: the
    router at the published expert count, this chip's share held."""
    from repro import config as C
    if (cfg["hidden_act"] != "silu" or cfg["attention_bias"]
            or cfg["tie_word_embeddings"] or cfg["scoring_func"] != "softmax"
            or cfg["topk_method"] != "group_limited_greedy"
            or cfg["moe_layer_freq"] != 1
            or cfg["rope_scaling"]["type"] != "yarn"):
        raise ValueError("the program's DeepSeek-V2 block is SwiGLU, "
                         "bias-free, softmax group-limited routing in every "
                         "layer after the dense ones, YaRN, untied head")
    dense = cfg["first_k_dense_replace"]
    y = cfg["rope_scaling"]
    return C.ArchConfig(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["v_head_dim"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        prefix_pattern=((C.ATTN_MLA, C.DENSE_FF),) * dense,
        layer_pattern=((C.ATTN_MLA, C.MOE_FF),),
        moe=C.MoEConfig(
            num_experts=cfg["published"]["n_routed_experts"],
            num_experts_per_tok=cfg["num_experts_per_tok"],
            num_shared_experts=cfg["n_shared_experts"],
            expert_d_ff=cfg["moe_intermediate_size"],
            n_group=cfg["n_group"], topk_group=cfg["topk_group"],
            norm_topk_prob=cfg["norm_topk_prob"],
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            first_expert=cfg["deployment"]["first_expert_held"],
            experts_held=cfg["n_routed_experts"]),
        mla=C.MLAConfig(kv_lora_rank=cfg["kv_lora_rank"],
                        q_lora_rank=cfg["q_lora_rank"],
                        qk_nope_head_dim=cfg["qk_nope_head_dim"],
                        qk_rope_head_dim=cfg["qk_rope_head_dim"],
                        v_head_dim=cfg["v_head_dim"]),
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=C.YarnConfig(
            factor=float(y["factor"]),
            original_max_position_embeddings=y[
                "original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=False, gated_ffn=True, dtype=cfg["torch_dtype"])


def run(r) -> None:
    import jax
    from repro.launch import serve
    from repro.serving import Request
    from repro.util import spans
    cfg, mix = r.cell.config, r.cell.traffic
    dep = cfg["serving"]
    arch = arch_config(cfg)
    weights = mla_moe_weights.make(cfg, r.seed, cfg["torch_dtype"])
    jax.block_until_ready(weights)
    eng = serve.start(arch, mla_moe_weights.program_tree(weights, cfg),
                      dep["block_table"], [], max_batch=dep["max_batch"],
                      max_len=dep["max_len"], page_size=dep["page_size"])
    eng.submit(Request.build(-1, np.ones(1, np.int32), max_new_tokens=1))
    while eng.sched.running or eng.sched.queue:
        eng.run(max_steps=1)

    clients = chat.clients(mix, cfg["vocab_size"], r.seed)
    inflight: Dict[int, Sent] = {}
    done: List[Sent] = []
    steps = []                        # (start, end, contexts processed)
    next_id = [0]
    compiles = r.compile_counter
    c0 = compiles.count

    def send(client, now, step):
        prompt, n_out = client.next()
        req = Request.build(next_id[0], prompt, max_new_tokens=n_out)
        next_id[0] += 1
        inflight[req.req_id] = Sent(req, client, now, step)
        eng.submit(req)

    if r.trace:
        spans.enable()
    # what set-up built (weights, the compiled step, the engine) stays
    # out of the cyclic collector's passes through the window, as a
    # serving process freezes its start-up objects
    gc.collect()
    gc.freeze()
    t0 = r.setup_done()
    for c in clients:
        send(c, 0.0, 0)
    trace_from = max(r.seconds / 2 - TRACE_SECONDS / 2, 0.0)
    tracing = None
    traced = [float("inf"), float("inf")]
    while True:
        now = time.perf_counter() - t0
        if r.trace and tracing is None and now >= trace_from:
            tracing = r.traced()
            tracing.__enter__()
            traced = [now, now + TRACE_SECONDS]
        s0 = now
        with r.span("bench.engine_step"):
            finished = eng.run(max_steps=1)
        s1 = time.perf_counter() - t0
        step = len(steps) + 1
        contexts = []
        for sid in list(eng.sched.running) + [f.req_id for f in finished]:
            s = inflight[sid]
            s.fed += 1
            contexts.append(s.fed)
            while len(s.token_times) < len(s.req.generated):
                if not s.token_times:
                    s.first_step = step
                s.token_times.append(s1)
        steps.append((s0, s1, contexts))
        with r.span("bench.clients"):
            for f in finished:
                s = inflight.pop(f.req_id)
                done.append(s)
                send(s.client, s1, step)
        if tracing and s1 >= traced[1]:
            tracing.__exit__(None, None, None)
            tracing, traced[1] = False, s1
        if s1 >= r.seconds:
            break
    if tracing:
        tracing.__exit__(None, None, None)
        traced[1] = steps[-1][1]
    gc.unfreeze()
    r.host.update(window_s=steps[-1][1], steps=steps, done=done,
                  inflight=list(inflight.values()),
                  window_compiles=compiles.count - c0,
                  traced_steps=[s for s in steps
                                if traced[0] <= s[0] and s[1] <= traced[1]])
    if r.trace:
        r.host["counters"] = spans.collect().counters
        spans.disable()
        r.host["step_hlo"] = step_text(eng, dep["block_table"])
    r.read_memory_peak()
    del eng
    check(r, cfg, mix, weights, done, list(inflight.values()))


def step_text(eng, kv_mode: str) -> str:
    """The compiled text of the decode step the engine ran (its arguments
    as the engine's state holds them after a step)."""
    import jax.numpy as jnp
    from repro.models import decode_step
    tokens = jnp.zeros((eng.max_batch,), jnp.int32)
    return decode_step.lower(
        eng.params, eng.cfg, eng.state, tokens,
        kv_mode=kv_mode).compile().as_text()


def check(r, cfg, mix, weights, done: List[Sent], inflight: List[Sent]):
    """A seeded sample of finished requests against the reference."""
    import jax
    import jax.numpy as jnp
    from reference import mla_moe_ref
    failed = [s for s in done + inflight if s.req.failed is not None]
    short = [s for s in done if len(s.req.generated) != s.req.max_new_tokens]
    r.attempted = len(done) + len(inflight)
    r.failed = len(failed) + len(short)
    k = min(mix["correctness_sample"], len(done))
    ok = r.compare("requests_failed", r.failed, 0)
    ok &= r.compare("no_finished_request", int(k == 0), 0)
    if k == 0:
        r.correct = False
        return
    tokens, positions, served, mask = sample(r.seed, mix, done, k)
    fn = jax.jit(lambda w, t, pos, sv: mla_moe_ref.served_gaps(
        w, cfg, t, pos, sv))
    with jax.default_matmul_precision("highest"):
        gaps = np.asarray(fn(weights, jnp.asarray(tokens),
                             jnp.asarray(positions), jnp.asarray(served)))
    r.host["sample"] = (tokens, positions, served, mask)
    r.host["weights"] = weights
    r.host["compared_tokens"] = int(mask.sum())
    r.host["compared_requests"] = k
    r.host["gaps"] = gaps[mask]
    for name, value in gap_stats(gaps[mask]).items():
        ok &= r.compare(name, value, LIMITS[name])
    r.correct = bool(ok)


def gap_stats(gaps: np.ndarray) -> Dict[str, float]:
    return {"served_gap_p95": float(np.percentile(gaps, 95)),
            "served_gaps_over_2": int((gaps > FAR).sum())}


def control(r) -> Dict[str, float]:
    """The float8 control's reading on the run's own sample: the widest
    gap, under the reference, of the tokens the control puts first."""
    import jax
    import jax.numpy as jnp
    from reference import mla_moe_ref
    tokens, positions, _, mask = r.host["sample"]
    cfg = r.cell.config
    fn = jax.jit(lambda w, t, pos: mla_moe_ref.control_gaps(w, cfg, t, pos))
    with jax.default_matmul_precision("highest"):
        gaps = np.asarray(fn(r.host["weights"], jnp.asarray(tokens),
                             jnp.asarray(positions)))
    r.host["control_gaps"] = gaps[mask]
    return gap_stats(gaps[mask])

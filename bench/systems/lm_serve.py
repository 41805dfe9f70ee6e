"""Paged-KV serving (``repro.serving.ServeEngine``) of a dense transformer.

Set-up makes the weights from the seed on the device, builds the engine
the configuration describes (``repro.launch.serve.start``) and serves one
one-token request through it, which compiles the decode step.  The
window drives the engine one step at a time (``run(max_steps=1)``) under
the mix's closed loop: each client sends its next request in the step
after its last one finished.  A token's time is the end of the engine
step that produced it.  The traced run traces the steps of a slice of a
few seconds from the middle of the window.

``correct``: once the window has closed and the engine is freed, a sample
of the requests that finished, drawn from the seed with the longest of
them in it, is run through the plain reference
(``bench/reference/lm_ref.py``) over prompt and served tokens; the widest
gap by which a served token's logit lies below the reference's best is
compared with its limit.  Every request that finished must have served
its full output.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import numpy as np

from harness import chat, lm_weights

#: widest gap (logits) of a served token below the reference's best; set
#: from the program's readings and the float8 control's (PERF.md section 2)
GAP_LIMIT = 0.25
#: seconds of the window that the traced run traces, from its middle
TRACE_SECONDS = 3.0


def arch_config(cfg: Dict):
    """The program's ArchConfig with the configuration's numbers."""
    from repro.config import ArchConfig
    if cfg["hidden_act"] != "silu" or cfg["bias"] or cfg["tie_word_embeddings"]:
        raise ValueError("the program's dense block is SwiGLU, bias-free, "
                         "with an untied head")
    return ArchConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=False, gated_ffn=True, dtype=cfg["torch_dtype"])


@dataclasses.dataclass
class Sent:
    req: object
    client: object
    sent: float
    sent_step: int
    fed: int = 0                      # engine steps this request took part in
    token_times: List[float] = dataclasses.field(default_factory=list)
    first_step: int = -1


def run(r) -> None:
    import jax
    from repro.launch import serve
    from repro.serving import Request
    cfg, mix = r.cell.config, r.cell.traffic
    dep = cfg["serving"]
    arch = arch_config(cfg)
    weights = lm_weights.make(cfg, r.seed)
    jax.block_until_ready(weights)
    eng = serve.start(arch, lm_weights.program_tree(weights),
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
    r.host.update(window_s=steps[-1][1], steps=steps, done=done,
                  inflight=list(inflight.values()),
                  window_compiles=compiles.count - c0,
                  traced_steps=[s for s in steps
                                if traced[0] <= s[0] and s[1] <= traced[1]])
    r.read_memory_peak()
    del eng
    check(r, cfg, mix, weights, done, list(inflight.values()))


def check(r, cfg, mix, weights, done: List[Sent], inflight: List[Sent]):
    """A seeded sample of finished requests against the reference."""
    import jax
    import jax.numpy as jnp
    from reference import lm_ref
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
    fn = jax.jit(lambda w, t, pos, sv: lm_ref.served_gaps(w, cfg, t, pos, sv))
    with jax.default_matmul_precision("highest"):
        gaps = np.asarray(fn(weights, jnp.asarray(tokens),
                             jnp.asarray(positions), jnp.asarray(served)))
    widest = float(gaps[mask].max())
    r.host["sample"] = (tokens, positions, served, mask)
    r.host["weights"] = weights
    r.host["compared_tokens"] = int(mask.sum())
    ok &= r.compare("served_gap_max", widest, GAP_LIMIT)
    r.correct = bool(ok)


def sample(seed: int, mix: Dict, done: List[Sent], k: int):
    """``k`` finished requests drawn from the seed, the longest among them,
    as padded (tokens, positions, served, mask) arrays."""
    longest = max(range(len(done)), key=lambda i: (
        len(done[i].req.generated), len(done[i].req.prompt)))
    rest = [i for i in range(len(done)) if i != longest]
    pick = [longest] + [int(i) for i in np.random.default_rng(
        [seed, 7]).choice(rest, k - 1, replace=False)]
    table = chat.length_table(mix)
    s_max = max(p + o for p, o in table)
    n_max = max(o for _, o in table)
    tokens = np.zeros((k, s_max), np.int32)
    positions = np.zeros((k, n_max), np.int32)
    served = np.zeros((k, n_max), np.int32)
    mask = np.zeros((k, n_max), bool)
    for row, i in enumerate(pick):
        req = done[i].req
        p, g = np.asarray(req.prompt), np.asarray(req.generated)
        seq = np.concatenate([p, g[:-1]])
        tokens[row, :len(seq)] = seq
        positions[row, :len(g)] = np.arange(len(p) - 1, len(p) - 1 + len(g))
        served[row, :len(g)] = g
        mask[row, :len(g)] = True
    return tokens, positions, served, mask


def control(r) -> Dict[str, float]:
    """The float8 control's reading on the run's own sample: the widest
    gap, under the reference, of the tokens the control puts first."""
    import jax
    import jax.numpy as jnp
    from reference import lm_ref
    tokens, positions, _, mask = r.host["sample"]
    cfg = r.cell.config
    fn = jax.jit(lambda w, t, pos: lm_ref.control_gaps(w, cfg, t, pos))
    with jax.default_matmul_precision("highest"):
        gaps = np.asarray(fn(r.host["weights"], jnp.asarray(tokens),
                             jnp.asarray(positions)))
    return {"served_gap_max": float(gaps[mask].max())}

"""ServeEngine: continuous-batching decode over the paged KV cache.

One unified step path: every live slot advances one token per engine step.
Slots still consuming their prompt are teacher-forced (the next prompt
token is fed regardless of the model's argmax); slots past their prompt
decode greedily.  Prompt feeding therefore exercises the exact same paged
append path as decoding — there is no separate prefill code to diverge.

Requests are admitted with ONE initial page; pages are allocated by the
scheduler as lengths grow (the OS role).  The kv table mode is either
pinned or occupancy-driven (the NDPage flatten decision).

Translation-costed mode: pass ``cost_model`` (a
:class:`repro.sim.cost_model.TranslationCostModel`) and every scheduler
step is priced under ALL simulated mechanisms at once — cache hits at
TLB-hit cost, misses at each mechanism's walk cost plus the touched-
PTE-line surcharge of the rebuilt row.  ONE decode loop serves every
mechanism (the mechanism never enters the jit, so nothing recompiles);
:meth:`ServeEngine.throughput` then reports tokens/sec per mechanism.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import block_table as BT
from repro.core.kv_page_manager import KVPageManager
from repro.models import decode_step, init_decode_state
from repro.serving._scheduler import BatchScheduler, Request
from repro.util import resilience, spans


@jax.jit
def _greedy(logits: jnp.ndarray) -> jnp.ndarray:
    """(B, V) logits -> (B,) int32 argmax, the first of tied maxima."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class ServeEngine:
    def __init__(self, cfg, params, *, max_batch: int = 8,
                 max_len: int = 256, page_size: int = 16,
                 table_mode: Optional[str] = None, cost_model=None):
        self.cfg = cfg
        self.params = params
        self.page_size = page_size
        self.max_len = max_len
        max_pages_total = max_batch * (-(-max_len // page_size)) + 8
        self.kvm = KVPageManager(max_pages_total, page_size, max_batch,
                                 max_len)
        self.meter = None
        if cost_model is not None:
            from repro.sim.cost_model import TranslationMeter
            self.meter = TranslationMeter(cost_model)
        self.sched = BatchScheduler(self.kvm, max_batch,
                                    table_mode=table_mode,
                                    meter=self.meter)
        self.max_batch = max_batch
        # the jit-side KV pools must cover every physical page id the
        # host allocator can hand out (ids at/past the pool corrupt KV
        # silently through clamped scatter)
        self.state = init_decode_state(cfg, max_batch, max_len,
                                       kv_mode=BT.FLAT, page_size=page_size,
                                       num_pages=max_pages_total)
        # per-slot prompt progress; _slot_prompt holds the stream being
        # teacher-forced (effective prompt snapshot taken at admission,
        # so a preempted request re-prefills prompt + prior tokens)
        self._prompt_pos = np.zeros(max_batch, np.int64)
        self._next_token = np.zeros(max_batch, np.int32)
        self._slot_prompt: List[Optional[np.ndarray]] = [None] * max_batch
        # inactive slots write their (discarded) K/V into a scratch page so
        # they can never alias a live sequence's pages
        self._scratch_page = self.kvm.pool.allocate(1)[0]

    # -- public ---------------------------------------------------------------
    def submit(self, req: Request) -> None:
        self.sched.submit(req)

    def run(self, max_steps: int = 10_000) -> List[Request]:
        finished: List[Request] = []
        for _ in range(max_steps):
            # the scheduler tick this iteration takes names its spans
            step = self.sched.clock + 1
            with spans.span("repro.serve.step", step=step):
                with spans.span("repro.serve.schedule", step=step):
                    self.sched.tick()
                    for slot, req in self.sched.admit():
                        # pages for the whole effective prompt were
                        # mapped at admission; teacher-force it from
                        # step 0 (for a resumed request that replays
                        # prompt + generated-so-far, so the KV cache is
                        # rebuilt bit-exactly before decode resumes)
                        self._slot_prompt[slot] = req.effective_prompt()
                        self._prompt_pos[slot] = 0
                        self._next_token[slot] = int(
                            self._slot_prompt[slot][0])
                if not self.sched.running and not self.sched.queue:
                    break
                if not self.sched.running:
                    continue
                finished.extend(self._engine_step())
        return finished

    def throughput(self) -> Dict:
        """Per-mechanism serving report (requires ``cost_model``):
        tokens/sec, accumulated translation cycles, the PER-STEP budget
        (mean/max over the meter's retained step window — misses make
        spiky steps), and the hit/miss tallies — one decode run priced
        under every mechanism."""
        if self.meter is None:
            raise ValueError("ServeEngine was built without a cost_model;"
                             " pass cost_model= to enable throughput()")
        m = self.meter
        return {
            "tokens_per_sec": m.tokens_per_sec(),
            "translation_cycles": m.translation_cycles(),
            "per_step_cycles": m.per_step_cycles(),
            "tokens": m.tokens, "steps": m.steps,
            "tcache_hits": m.hits, "tcache_misses": m.misses,
        }

    # -- internals --------------------------------------------------------------
    def _engine_step(self) -> List[Request]:
        step = self.sched.clock          # this iteration's tick
        # injected mid-decode eviction (the evict_storm chaos plan):
        # preempt the scheduler's victim of choice before the step runs;
        # greedy re-prefill makes the final tokens bit-exact anyway
        inj = resilience.fault_injector()
        if inj is not None and self.sched.running and inj.fires("evict"):
            self.sched.preempt(self.sched.pick_victim(), reason="fault")
            if not self.sched.running:
                return []
        tc = self.sched.tcache
        with spans.span("repro.serve.tables", step=step) as sp:
            hits, misses = tc.hits, tc.misses
            mode, table, lens = self._build_tables()
            tokens = jnp.asarray(self._next_token)
            sp.set(rows=tc.misses - misses, hits=tc.hits - hits)
            # what each layer's paged-attention call fetches (the pages
            # of every slot's live range, the new token's included) out
            # of what a dense walk of the table would; K/V pages for GQA,
            # latent pages for MLA, which share the table
            spans.count("repro.serve.kv_pages_live",
                        int((-(-(lens + 1) // self.page_size)).sum()))
            spans.count("repro.serve.kv_pages_table",
                        self.max_batch * self.kvm.max_pages)
            lens = jnp.asarray(lens)
        state = dict(self.state)
        state["table"] = table
        state["lengths"] = lens
        with spans.span("repro.serve.dispatch", step=step):
            logits, new_state = decode_step(self.params, self.cfg, state,
                                            tokens, kv_mode=mode)
            self.state = dict(new_state)
            # greedy choice on the device: the host fetches one id a
            # slot, not the (B, V) logits
            chosen = _greedy(logits)
        with spans.span("repro.serve.device_wait", step=step):
            rows = new_state.get("moe_rows_held")
            if rows is None:
                chosen = np.asarray(chosen)
            else:
                # (token, expert) pairs the held experts computed this
                # step, over the layers: in the ids' transfer
                chosen, rows = jax.device_get((chosen, rows))
                spans.count("repro.serve.moe_rows_held", int(rows))

        with spans.span("repro.serve.sample", step=step):
            produced: Dict[int, int] = {}
            for sid in self.sched.active_seqs():
                slot = self.sched.slot_of[sid]
                self._prompt_pos[slot] += 1
                pos = self._prompt_pos[slot]
                stream = self._slot_prompt[slot]
                if pos < len(stream):
                    # teacher-forced prompt consumption
                    self._next_token[slot] = int(stream[pos])
                else:
                    nxt = int(chosen[slot])
                    self._next_token[slot] = nxt
                    produced[sid] = nxt
            return self.sched.record_tokens(produced)

    def _build_tables(self):
        mode, rows, _ = self.sched.step_tables()
        flat = np.full((self.max_batch, self.kvm.max_pages),
                       self._scratch_page, np.int32)
        lens = np.zeros((self.max_batch,), np.int32)
        for row, sid in zip(rows, self.sched.active_seqs()):
            slot = self.sched.slot_of[sid]
            flat[slot] = row
            # the model writes the CURRENT token at cache index `lengths`;
            # exactly prompt_pos tokens are materialized (prompt_pos counts
            # every engine step this slot has taken)
            lens[slot] = int(self._prompt_pos[slot])
        table = jnp.asarray(flat)
        if mode == BT.RADIX:
            table = BT.radix_from_flat(
                table, leaf_size=min(16, self.kvm.max_pages))
        return mode, table, lens


def greedy_reference(cfg, params, prompt: np.ndarray, new_tokens: int,
                     kv_mode: str = "dense", max_len: int = 256,
                     page_size: int = 16) -> List[int]:
    """Single-sequence greedy decode without the scheduler (oracle for
    engine tests)."""
    from repro.models import prefill
    logits, state = prefill(params, cfg, jnp.asarray(prompt[None]),
                            kv_mode=kv_mode, max_len=max_len,
                            page_size=page_size)
    out = []
    tok = int(np.argmax(np.asarray(logits)[0]))
    out.append(tok)
    for _ in range(new_tokens - 1):
        logits, state = decode_step(params, cfg, state,
                                    jnp.asarray([tok], np.int32),
                                    kv_mode=kv_mode)
        tok = int(np.argmax(np.asarray(logits)[0]))
        out.append(tok)
    return out

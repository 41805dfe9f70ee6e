"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole tiny run on the CPU (the look for a chip
skipped) with one fault planted in the program: a step that returns its
state unchanged, half of the batch left out, an answer altered where it
is produced.  The exchange between chips does not exist in these cells:
the simulator's lanes never communicate and serving runs on one chip.
"""
import dataclasses

import jax

from conftest import run_tiny


# --- the simulator ----------------------------------------------------------
def test_sim_state_unchanged(tiny_bench, monkeypatch):
    from repro.sim import simulator
    monkeypatch.setattr(simulator, "_chunk_runner", lambda *a, **k: jax.jit(
        lambda state, xs, mt, dp: state))
    assert not run_tiny(tiny_bench, "t.sim")["correct"]


def _wrap_batch(monkeypatch, change):
    import repro.sim
    real = repro.sim.simulate_batch

    def broken(mach, traces, *a, **k):
        return change(real, mach, traces, *a, **k)
    monkeypatch.setattr(repro.sim, "simulate_batch", broken)


def test_sim_half_the_batch(tiny_bench, monkeypatch):
    def half(real, mach, traces, *a, **k):
        res = real(mach, traces[: len(traces) // 2 + 1], *a, **k)
        return (res * len(traces))[: len(traces)]
    _wrap_batch(monkeypatch, half)
    assert not run_tiny(tiny_bench, "t.sim")["correct"]


def test_sim_answer_altered(tiny_bench, monkeypatch):
    def altered(real, mach, traces, *a, **k):
        res = real(mach, traces, *a, **k)
        walks = res[-1].walks.copy()
        walks[0, 0] += 1
        res[-1] = dataclasses.replace(res[-1], walks=walks)
        return res
    _wrap_batch(monkeypatch, altered)
    out = run_tiny(tiny_bench, "t.sim")
    assert not out["correct"]
    assert out["compared"]["counter_mismatches"]["value"] > 0


# --- serving ------------------------------------------------------------------
def _wrap_decode(monkeypatch, change):
    from repro.serving import _engine
    real = _engine.decode_step

    def broken(params, cfg, state, tokens, kv_mode="dense"):
        return change(real, params, cfg, state, tokens, kv_mode)
    monkeypatch.setattr(_engine, "decode_step", broken)


def test_serve_state_unchanged(tiny_bench, monkeypatch):
    def frozen(real, params, cfg, state, tokens, kv_mode):
        logits, _ = real(params, cfg, state, tokens, kv_mode=kv_mode)
        return logits, state
    _wrap_decode(monkeypatch, frozen)
    assert not run_tiny(tiny_bench, "t.serve", seconds=2.0)["correct"]


def test_serve_half_the_batch(tiny_bench, monkeypatch):
    def half(real, params, cfg, state, tokens, kv_mode):
        logits, new = real(params, cfg, state, tokens, kv_mode=kv_mode)
        b = logits.shape[0]
        return logits.at[b // 2:].set(0.0), new
    _wrap_decode(monkeypatch, half)
    assert not run_tiny(tiny_bench, "t.serve", seconds=2.0)["correct"]


def test_serve_token_altered(tiny_bench, monkeypatch):
    from repro.serving import _engine
    real = _engine.ServeEngine._engine_step

    def altered(self):
        finished = real(self)
        for req in finished:
            req.generated[-1] = (req.generated[-1] + 1) % self.cfg.vocab_size
        return finished
    monkeypatch.setattr(_engine.ServeEngine, "_engine_step", altered)
    assert not run_tiny(tiny_bench, "t.serve", seconds=2.0)["correct"]

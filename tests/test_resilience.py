"""Resilience layer: integrity-checked caches, fault injection,
watchdog retries, sweep crash-resume, stage-timeout isolation.

The invariant under test everywhere: a fault may cost retries, never
answers — recovered runs are bit-exact with fault-free runs.
"""
import os
import time

import numpy as np
import pytest

from repro.util import resilience

#: chunk length unique to this file so runner-cache compile accounting
#: is exact (the cache is shared process-wide; see test_sweep.py)
CHUNK_CKPT = 288
LEN_CKPT = 600


@pytest.fixture(autouse=True)
def _clean_events():
    resilience.recovery_events(clear=True)
    yield
    resilience.recovery_events(clear=True)


# ---------------------------------------------------------------------------
# integrity-checked byte store
# ---------------------------------------------------------------------------
class TestIntegrityStore:
    def test_roundtrip_writes_sidecar(self, tmp_path):
        p = str(tmp_path / "entry.bin")
        assert resilience.write_bytes(p, b"payload")
        assert os.path.exists(p + resilience.SIDECAR_SUFFIX)
        assert resilience.read_bytes(p) == b"payload"

    def test_bitflip_quarantines(self, tmp_path):
        p = str(tmp_path / "entry.bin")
        resilience.write_bytes(p, b"payload-payload")
        raw = bytearray(open(p, "rb").read())
        raw[3] ^= 0x40                       # single bit flip
        with open(p, "wb") as f:
            f.write(raw)
        assert resilience.read_bytes(p) is None
        assert not os.path.exists(p)
        qdir = tmp_path / resilience.QUARANTINE_DIR
        assert (qdir / "entry.bin").exists()
        kinds = [k for k, _ in resilience.recovery_events()]
        assert "quarantine" in kinds

    def test_missing_sidecar_serves_unverified(self, tmp_path):
        # legacy entries predating the sidecar format still load
        p = str(tmp_path / "old.bin")
        with open(p, "wb") as f:
            f.write(b"legacy")
        assert resilience.read_bytes(p) == b"legacy"

    def test_corrupt_npz_quarantined(self, tmp_path):
        p = str(tmp_path / "arr.npz")
        resilience.write_npz(p, {"x": np.arange(5)})
        # truncate PAST the sha check by rewriting payload+sidecar
        resilience.write_bytes(p, b"PK\x03\x04 not a real zip")
        assert resilience.read_npz(p) is None
        assert (tmp_path / resilience.QUARANTINE_DIR / "arr.npz").exists()

    def test_write_fault_degrades_to_cache_off(self, tmp_path):
        p = str(tmp_path / "w.bin")
        inj = resilience.FaultInjector(
            [resilience.Fault("cache_write", at=(0,))])
        with resilience.inject_faults(inj):
            assert resilience.write_bytes(p, b"x") is False
        assert not os.path.exists(p)
        kinds = [k for k, _ in resilience.recovery_events()]
        assert "cache_off" in kinds


# ---------------------------------------------------------------------------
# the trace cache degrades, never crashes (the ISSUE regression)
# ---------------------------------------------------------------------------
class TestTraceCacheDegrade:
    def _gen(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SIM_TRACE_CACHE", str(tmp_path))
        from repro.workloads import generate_trace
        return lambda: generate_trace("rnd", 2, length=512, seed=3)

    def test_bitflipped_npz_recomputes_bit_exact(self, tmp_path,
                                                 monkeypatch):
        gen = self._gen(tmp_path, monkeypatch)
        clean = gen()
        entries = [f for f in os.listdir(tmp_path)
                   if f.endswith(".npz")]
        assert len(entries) == 1
        path = tmp_path / entries[0]
        raw = bytearray(path.read_bytes())
        raw[len(raw) // 2] ^= 0x01
        path.write_bytes(raw)
        again = gen()                       # quarantine + recompute
        for k in ("vpn", "off", "work"):
            np.testing.assert_array_equal(clean[k], again[k])
        assert (tmp_path / resilience.QUARANTINE_DIR
                / entries[0]).exists()
        kinds = [k for k, _ in resilience.recovery_events()]
        assert "quarantine" in kinds

    def test_truncated_npz_recomputes(self, tmp_path, monkeypatch):
        gen = self._gen(tmp_path, monkeypatch)
        clean = gen()
        entries = [f for f in os.listdir(tmp_path)
                   if f.endswith(".npz")]
        path = tmp_path / entries[0]
        # truncation with a stale sidecar -> sha mismatch path
        path.write_bytes(path.read_bytes()[:64])
        again = gen()
        np.testing.assert_array_equal(clean["vpn"], again["vpn"])


# ---------------------------------------------------------------------------
# fault injection is deterministic
# ---------------------------------------------------------------------------
class TestFaultInjector:
    def test_fires_on_listed_occurrences_only(self):
        inj = resilience.FaultInjector(
            [resilience.Fault("evict", at=(0, 2))])
        assert [inj.fires("evict") for _ in range(4)] == [
            True, False, True, False]

    def test_match_scopes_the_counter(self):
        inj = resilience.FaultInjector(
            [resilience.Fault("dispatch", at=(0,), match="bucket1")])
        assert not inj.fires("dispatch", "bucket0")
        assert inj.fires("dispatch", "bucket1")

    def test_unknown_site_rejected(self):
        with pytest.raises(ValueError, match="site"):
            resilience.Fault("frobnicate", at=(0,))

    def test_named_plans_exist(self):
        for name in ("cache_corrupt", "dispatch_hang", "evict_storm"):
            inj = resilience.FaultInjector.from_plan(name)
            assert inj.faults


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------
class TestWatchdog:
    def test_returns_result_under_deadline(self):
        assert resilience.watchdog_call(lambda: 7, 5.0) == 7

    def test_real_hang_times_out_then_retries(self):
        calls = []

        def fn():
            calls.append(1)
            if len(calls) == 1:
                time.sleep(3)
            return "ok"

        assert resilience.watchdog_call(fn, 0.2, tag="t",
                                        retries=1) == "ok"
        assert len(calls) == 2
        kinds = [k for k, _ in resilience.recovery_events()]
        assert "watchdog_timeout" in kinds and "watchdog_retry" in kinds

    def test_exhausted_retries_propagate(self):
        def hang():
            time.sleep(3)

        with pytest.raises(resilience.DispatchTimeout):
            resilience.watchdog_call(hang, 0.2, retries=0)

    def test_inline_mode_retries_injected_timeouts(self):
        inj = resilience.FaultInjector(
            [resilience.Fault("dispatch", at=(0,))])
        calls = []

        def fn():
            calls.append(1)
            if inj.fires("dispatch"):
                raise resilience.DispatchTimeout("injected")
            return 42

        # timeout_s <= 0: inline, only injected timeouts fire
        assert resilience.watchdog_call(fn, 0, retries=1) == 42
        assert len(calls) == 2


# ---------------------------------------------------------------------------
# sweep crash-resume: finished buckets never re-dispatch
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestSweepCheckpoint:
    GRID = {"memory.latency": (100, 170), "pwc_entries": (16, 32)}

    def _sweep(self, **kw):
        from repro.sim import sweep
        return sweep(self.GRID, cores=2, trace_len=LEN_CKPT,
                     chunk=CHUNK_CKPT, **kw)

    def test_resume_skips_finished_buckets(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SIM_TRACE_CACHE", str(tmp_path))
        r1 = self._sweep(checkpoint=True)
        assert r1.stats["buckets"] == 2        # one per pwc_entries
        assert r1.stats["runner_compiles"] == 2  # fresh chunk -> exact
        ckpts = sorted(f for f in os.listdir(tmp_path)
                       if f.startswith("sweepckpt_")
                       and f.endswith(".npz"))
        assert len(ckpts) == 2

        # simulate a crash after bucket 0: drop bucket 1's checkpoint
        os.remove(tmp_path / ckpts[1])
        os.remove(str(tmp_path / ckpts[1]) + resilience.SIDECAR_SUFFIX)
        from repro.sim.simulator import clear_runner_cache
        clear_runner_cache()                  # cold engine, warm ckpt
        r2 = self._sweep(checkpoint=True)
        assert r2.stats["resumed_buckets"] == 1
        assert r2.stats["runner_compiles"] == 1   # ONLY the lost bucket
        resumed = [b for b in r2.stats["per_bucket"] if b.get("resumed")]
        assert len(resumed) == 1 and resumed[0]["compiles"] == 0
        for a, b in zip(r1.results.flat, r2.results.flat):
            np.testing.assert_array_equal(a.cycles, b.cycles)
            np.testing.assert_array_equal(a.walk_cycles, b.walk_cycles)
        kinds = [k for k, _ in resilience.recovery_events()]
        assert "resume" in kinds

    def test_corrupt_checkpoint_redispatches(self, tmp_path,
                                             monkeypatch):
        monkeypatch.setenv("SIM_TRACE_CACHE", str(tmp_path))
        r1 = self._sweep(checkpoint=True)
        ckpts = sorted(f for f in os.listdir(tmp_path)
                       if f.startswith("sweepckpt_")
                       and f.endswith(".npz"))
        p = tmp_path / ckpts[0]
        raw = bytearray(p.read_bytes())
        raw[10] ^= 0xFF
        p.write_bytes(raw)
        r2 = self._sweep(checkpoint=True)     # quarantine + re-dispatch
        assert r2.stats["resumed_buckets"] == 1   # the intact one
        for a, b in zip(r1.results.flat, r2.results.flat):
            np.testing.assert_array_equal(a.cycles, b.cycles)

    def test_checkpoint_off_by_default(self, tmp_path, monkeypatch):
        monkeypatch.setenv("SIM_TRACE_CACHE", str(tmp_path))
        monkeypatch.delenv("SIM_SWEEP_CHECKPOINT", raising=False)
        self._sweep()
        assert not [f for f in os.listdir(tmp_path)
                    if f.startswith("sweepckpt_")]

    def test_injected_dispatch_fault_is_retried_bit_exact(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("SIM_TRACE_CACHE", str(tmp_path))
        clean = self._sweep()
        inj = resilience.FaultInjector.from_plan("dispatch_hang")
        with resilience.inject_faults(inj):
            faulted = self._sweep()
        for a, b in zip(clean.results.flat, faulted.results.flat):
            np.testing.assert_array_equal(a.cycles, b.cycles)
        kinds = [k for k, _ in resilience.recovery_events()]
        assert "watchdog_retry" in kinds


# ---------------------------------------------------------------------------
# runner cache counter stays monotone across clears
# ---------------------------------------------------------------------------
def test_runner_cache_misses_monotone_across_clear():
    from repro.sim.simulator import clear_runner_cache, runner_cache_info
    before = runner_cache_info().misses
    clear_runner_cache()
    assert runner_cache_info().misses >= before


# ---------------------------------------------------------------------------
# benchmark driver: a hanging stage is TIMEOUT, not FAIL, exit nonzero
# ---------------------------------------------------------------------------
class TestStageTimeout:
    def test_hanging_stage_reports_timeout(self, tmp_path, monkeypatch,
                                           capsys):
        from benchmarks import run as bench_run
        from benchmarks import sim_figures

        def hang():
            time.sleep(5)

        monkeypatch.setattr(sim_figures, "run_all", hang)
        monkeypatch.chdir(tmp_path)           # stray outputs go here
        with pytest.raises(SystemExit) as exc:
            bench_run.main(["--stage-timeout", "0.3"])
        assert exc.value.code != 0
        out = capsys.readouterr().out
        assert "TIMEOUT" in out and "figures" in out
        assert "FAIL    figures" not in out

    def test_failing_stage_still_fail_not_timeout(self, tmp_path,
                                                  monkeypatch, capsys):
        from benchmarks import run as bench_run
        from benchmarks import sim_figures

        def boom():
            raise RuntimeError("broken stage")

        monkeypatch.setattr(sim_figures, "run_all", boom)
        monkeypatch.chdir(tmp_path)
        with pytest.raises(SystemExit) as exc:
            bench_run.main(["--stage-timeout", "30"])
        assert exc.value.code != 0
        out = capsys.readouterr().out
        assert "FAIL" in out and "TIMEOUT" not in out

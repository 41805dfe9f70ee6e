"""repro.util.spans, and the spans, counters and scopes the serving engine
and the simulator put on it: off by default and free of clock reads
there, nesting and parents, self time, the ring's bound, and results
unchanged whether recording is on or off."""
import dataclasses
import re
import time

import jax
import numpy as np
import pytest

from repro.config import get_arch, smoke_variant
from repro.configs.ndp_sim import ndp_machine
from repro.models import init_params
from repro.serving import BatchScheduler, Request, ServeEngine
from repro.sim import simulator as S
from repro.util import spans


@pytest.fixture
def recording():
    spans.enable()
    try:
        yield
    finally:
        spans.disable()


def test_off_is_a_shared_no_op(monkeypatch):
    spans.disable()
    before = spans.collect()

    def forbidden(*a, **k):
        raise AssertionError("read while recording is off")

    monkeypatch.setattr(time, "perf_counter_ns", forbidden)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", forbidden)
    a = spans.span("repro.test.a")
    b = spans.span("repro.test.b", step=3)
    assert a is b
    with a as sp:
        sp.set(rows=1)
        spans.count("repro.test.n", 5)
    monkeypatch.undo()
    assert spans.collect() == before


def test_on_records_nesting_counters_and_self_time(recording):
    with spans.span("repro.test.outer", step=1) as outer:
        with spans.span("repro.test.inner", step=1) as inner:
            time.sleep(0.002)
            inner.set(rows=4)
        with spans.span("repro.test.inner", step=1):
            spans.count("repro.test.tokens", 3)
            spans.count("repro.test.tokens")
    got = spans.collect()
    assert [s.name for s in got.spans] == [
        "repro.test.inner", "repro.test.inner", "repro.test.outer"]
    o = got.named("repro.test.outer")[0]
    kids = got.children(o)
    assert o.parent_id is None and len(kids) == 2
    assert all(k.parent_id == o.span_id for k in kids)
    assert kids[0].attrs == {"step": 1, "rows": 4}
    assert all(o.start_ns <= k.start_ns <= k.end_ns <= o.end_ns
               for k in kids)
    assert got.self_time(o) == o.duration_ns - sum(
        k.duration_ns for k in kids)
    assert got.self_time(kids[0]) == kids[0].duration_ns >= 2_000_000
    assert got.counters == {"repro.test.tokens": 4}
    assert outer.seconds == pytest.approx(o.duration_ns * 1e-9)
    assert got.dropped == 0


def test_clock_span_times_without_recording():
    spans.disable()
    before = spans.collect()
    with spans.span("repro.test.timed", clock=True) as sp:
        time.sleep(0.001)
    assert sp.seconds >= 1e-3
    assert spans.collect() == before


def test_ring_bound_holds(recording):
    extra = 7
    for i in range(spans.RING + extra):
        with spans.span("repro.test.s", i=i):
            pass
    got = spans.collect()
    assert len(got.spans) == spans.RING
    assert got.dropped == extra
    assert got.spans[0].attrs["i"] == extra     # the oldest went


# -- serving ------------------------------------------------------------------
CFG = dataclasses.replace(smoke_variant(get_arch("internlm2-1.8b")),
                          dtype="float32")
STEP_CHILDREN = ["repro.serve.schedule", "repro.serve.tables",
                 "repro.serve.dispatch", "repro.serve.device_wait",
                 "repro.serve.sample"]


def _serve():
    params = init_params(CFG, jax.random.PRNGKey(0))
    eng = ServeEngine(CFG, params, max_batch=2, max_len=48, page_size=8)
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(Request.build(i, rng.integers(1, CFG.vocab_size, 4),
                                 max_new_tokens=3))
    done = eng.run()
    return {r.req_id: list(r.generated) for r in done}


def test_engine_step_spans_and_tokens_on_and_off():
    spans.disable()
    off = _serve()
    spans.enable()
    try:
        on = _serve()
    finally:
        spans.disable()
    assert on == off and len(on) == 3
    got = spans.collect()
    steps = got.named("repro.serve.step")
    worked = [s for s in steps if len(got.children(s)) > 1]
    assert worked
    for s in worked:
        kids = got.children(s)
        assert [k.name for k in kids] == STEP_CHILDREN
        assert all(k.attrs["step"] == s.attrs["step"] for k in kids)
    tables = got.named("repro.serve.tables")
    assert all({"rows", "hits"} <= set(t.attrs) for t in tables)
    assert sum(t.attrs["rows"] for t in tables) >= 3    # one per request
    assert sum(t.attrs["hits"] for t in tables) > 0
    # every step fetches at least a page a slot (2 slots of 6 pages)
    live = got.counters["repro.serve.kv_pages_live"]
    assert 2 * len(tables) <= live < got.counters["repro.serve.kv_pages_table"]
    assert got.counters["repro.serve.kv_pages_table"] == 12 * len(tables)


def test_scheduler_records_queue_wait_ticks():
    from repro.core.kv_page_manager import KVPageManager
    kvm = KVPageManager(16, page_size=4, max_seqs=1, max_len=16)
    sched = BatchScheduler(kvm, max_batch=1)
    reqs = [Request.build(i, np.ones(3, np.int32), max_new_tokens=1)
            for i in range(2)]
    for r in reqs:
        sched.submit(r)
    for _ in range(3):
        sched.tick()
        sched.admit()
        sched.step_tables()
        sched.record_tokens({sid: 7 for sid in sched.running})
    assert [r.submit_tick for r in reqs] == [0, 0]
    assert [r.admit_tick for r in reqs] == [1, 2]


# -- simulator -----------------------------------------------------------------
def _sim_jobs(n=3, cores=2, length=300):
    rng = np.random.default_rng(1)
    mach = ndp_machine(cores)
    traces = [{"vpn": rng.integers(0, 1 << 14, (cores, length)),
               "off": rng.integers(0, 64, (cores, length)),
               "work": rng.integers(0, 4, (cores, length))}
              for _ in range(n)]
    return mach, traces


def test_simulate_batch_spans_results_and_timings():
    mach, traces = _sim_jobs()
    chunk = 128
    spans.disable()
    tm_off = {}
    off = S.simulate_batch(mach, traces, chunk=chunk, timings=tm_off)
    spans.enable()
    try:
        tm_on = {}
        on = S.simulate_batch(mach, traces, chunk=chunk, timings=tm_on)
    finally:
        spans.disable()
    for a, b in zip(off, on):
        for f in dataclasses.fields(a):
            np.testing.assert_array_equal(getattr(a, f.name),
                                          getattr(b, f.name))
    keys = {"compile_s", "run_s", "total_s", "chunks", "pack_s",
            "transfer_s"}
    assert set(tm_off) == set(tm_on) == keys
    assert tm_on["chunks"] == 3
    assert tm_on["total_s"] == tm_on["compile_s"] + tm_on["run_s"]
    got = spans.collect()
    job, = got.named("repro.sim.job")
    assert [k.name for k in got.children(job)] == [
        "repro.sim.pack", "repro.sim.transfer", "repro.sim.compile",
        "repro.sim.run", "repro.sim.readback"]
    run, = got.named("repro.sim.run")
    assert [k.name for k in got.children(run)] == (
        ["repro.sim.chunk"] * 3 + ["repro.sim.sync"])
    assert all(s.attrs["lanes"] == 3 for s in got.spans)
    assert tm_on["run_s"] == pytest.approx(run.duration_ns * 1e-9)


def test_chunk_runner_carries_the_scopes():
    mach, traces = _sim_jobs(n=2, length=64)
    jobs = [S.SimJob(mach, tr) for tr in traces]
    text = S.lower_batch(jobs, chunk=64).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in S.SCOPES:
        assert any(re.search(rf"\b{scope}\b", n) for n in names), scope

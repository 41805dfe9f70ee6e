"""The simulator's scan step by part (``harness/scopes.py``): the map from
the compiled runner's instructions to the program's named scopes, the
device time per scope on a small trace kept under ``bench/fixtures/``,
and the readers of the per-layer metrics this map and the program's
timings feed.

``scoped_scan.xspace.pbtxt`` is written by hand in the profiler's XSpace
format: two scan steps of a TLB, a walk and a data fusion and an
unscoped copy inside the runner's while op, and an op of another program
after the loop that shares a fusion's name.
"""
import os
import types

import pytest

from conftest import BENCH, make_tiny_bench
from harness import scopes, tracing
from harness.core import BenchError, Cell, load_module

FIXTURE = os.path.join(BENCH, "fixtures", "scoped_scan.xspace.pbtxt")
#: the instructions of the fixture's runner, as its compiled text gives
#: them (one line each, metadata kept)
HLO = """\
  %while.6 = (s32[], s32[88,5,16,4]{0,2,3,1}) while((s32[], s32[88,5,16,4]{0,2,3,1}) %tuple.3), condition=%cond, body=%body, metadata={op_name="jit(run_batch)/while"}
  %fusion.10 = s32[88,5,16,4]{0,2,3,1} fusion(s32[88,5,16,4]{0,2,3,1} %p), kind=kLoop, calls=%fused_computation.10, metadata={op_name="jit(run_batch)/while/body/closed_call/vmap(vmap(tlb))/scatter" source_file="simulator.py" source_line=1}
  %fusion.11 = s32[88,5,4,32]{0,3,2,1} fusion(s32[88,5,4,32]{0,3,2,1} %q), kind=kLoop, calls=%fused_computation.11, metadata={op_name="jit(run_batch)/while/body/closed_call/vmap(vmap(walk))/scatter"}
  ROOT %fusion.12 = s32[88,5,64,8]{0,3,2,1} fusion(s32[88,5,64,8]{0,3,2,1} %r), kind=kLoop, calls=%fused_computation.12, metadata={op_name="jit(run_batch)/while/body/closed_call/vmap(vmap(data))/scatter"}
  %copy-done.3 = s32[88,5]{0,1} copy-done((s32[88,5]{0,1}, s32[88,5]{0,1}, u32[]) %copy-start.3)
  %broadcast.7 = s32[88,5]{0,1} broadcast(s32[] %c), dimensions={}, metadata={op_name="jit(run_batch)/broadcast_in_dim"}
"""


def _fixture_summary():
    from jax.profiler import ProfileData
    with open(FIXTURE) as f:
        return tracing.reduce_profile(ProfileData.from_text_proto(f.read()),
                                      chips=1)


def test_op_scope_and_instruction_map():
    assert scopes.op_scope(
        "jit(run_batch)/while/body/closed_call/vmap(vmap(walk))/gather"
    ) == "walk"
    assert scopes.op_scope("jit(run_batch)/while/body/squeeze") is None
    assert scopes.instruction_scopes(HLO) == {
        "while.6": None, "fusion.10": "tlb", "fusion.11": "walk",
        "fusion.12": "data", "copy-done.3": None, "broadcast.7": None}
    assert scopes.instruction_name(
        "%fusion.10 = s32[88,5,16,4]{0,2,3,1:T(8,128)} fusion(...)"
    ) == "fusion.10"
    assert scopes.instruction_name("fusion.10") == "fusion.10"


def test_scope_seconds_on_the_fixture():
    ts = _fixture_summary()
    secs, steps = scopes.scope_seconds(ts, scopes.instruction_scopes(HLO))
    # fusion.11 after the loop belongs to another program: not counted
    assert secs == {"tlb": pytest.approx(4e-6), "walk": pytest.approx(6e-6),
                    "data": pytest.approx(2e-6)}
    assert steps == 2                 # each body instruction ran twice
    # a program without the scopes: an empty map, nothing to read
    assert scopes.scope_seconds(ts, {}) == ({}, 0.0)


def test_scope_seconds_refuses_another_program():
    """The traced loop must be the compiled runner's: an op inside it
    that the compiled text lacks, or no loop of the runner at all, is an
    error rather than a silently smaller part."""
    ts = _fixture_summary()
    names = scopes.instruction_scopes(HLO)
    lacking = {k: v for k, v in names.items() if k != "copy-done.3"}
    with pytest.raises(BenchError, match="copy-done.3"):
        scopes.scope_seconds(ts, lacking)
    no_loop = {k: v for k, v in names.items() if k != "while.6"}
    with pytest.raises(BenchError, match="no while op"):
        scopes.scope_seconds(ts, no_loop)


def test_outermost_loops():
    assert scopes._outermost([(5, 6), (0, 10), (2, 3), (12, 14)]) == [
        [0, 10], [12, 14]]


def _run(ts):
    return types.SimpleNamespace(
        trace_summary=ts,
        host={"runner_scopes": scopes.instruction_scopes(HLO)})


@pytest.mark.parametrize("scope,us", [("tlb", 2.0), ("walk", 3.0),
                                      ("data", 1.0)])
def test_scope_readers(scope, us):
    mod = load_module(os.path.join(BENCH, "metrics",
                                   f"sim.{scope}_us_per_step.py"),
                      f"bench_metric_sim_{scope}_us_per_step")
    # the fixture's loop runs two scan steps
    assert mod.read(_run(_fixture_summary())) == pytest.approx(us)
    assert mod.read(_run(None)) is None
    empty = _run(_fixture_summary())
    empty.host["runner_scopes"] = {}
    assert mod.read(empty) is None


def test_runner_scopes_of_a_tiny_cell(tmp_path):
    """The map the readers compile from the program for the cell's own
    job carries every scope."""
    cell = Cell("t.sim", bench_dir=str(make_tiny_bench(tmp_path)))
    run = types.SimpleNamespace(cell=cell, seed=7, host={})
    assert set(scopes.runner_scopes(run).values()) == {*scopes.SCOPES, None}


def test_prepare_ms_reader():
    mod = load_module(os.path.join(BENCH, "metrics", "sim.prepare_ms.py"),
                      "bench_metric_sim_prepare_ms")
    jobs = [{"timings": {"pack_s": p, "transfer_s": 0.001}}
            for p in (0.002, 0.004, 0.009)]
    assert mod.read(types.SimpleNamespace(host={"jobs": jobs})) == (
        pytest.approx(5.0))
    # a program without the pack and transfer spans: nothing to read
    old = [{"timings": {"run_s": 1.0}}]
    assert mod.read(types.SimpleNamespace(host={"jobs": old})) is None

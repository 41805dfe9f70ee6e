"""The reduction from a profiler trace to busy time, idle share, kernel
time and the breakdown: on synthetic intervals, and on a small trace
kept under ``bench/fixtures/``.

``three_steps.xspace.pbtxt`` is written by hand in the profiler's own
XSpace format and read through ``jax.profiler.ProfileData``, the path a
real trace takes: three engine steps on one TPU, each the kernel
wrapper's 2 us pool transpose (whose statistics name the wrapper), the
5 us paged kernel and a 1 us fusion, in a 40 us ``bench.traced`` window.
"""
import os

import numpy as np
import pytest

from conftest import BENCH
from harness import tracing

FIXTURE = os.path.join(BENCH, "fixtures", "three_steps.xspace.pbtxt")


def test_union_length_and_merge():
    iv = np.array([[0, 2], [1, 3], [5, 6], [5.5, 5.7]], float)
    assert tracing.union_length(iv) == pytest.approx(4.0)
    assert tracing.merged(iv) == [(0.0, 3.0), (5.0, 6.0)]
    assert tracing.union_length(np.zeros((0, 2))) == 0.0


def test_summary_on_synthetic_ops():
    ops = [[("fusion.1", 10.0, 20.0),
            ("paged_attention_pallas.9", 15.0, 40.0),
            ("custom-call.13", 60.0, 70.0)]]
    spans = [("bench.traced", 0.0, 100.0), ("bench.step", 5.0, 45.0),
             ("bench.sleep", 45.0, 59.0)]
    s = tracing.TraceSummary((0.0, 100.0), ops, spans)
    assert s.busy_s == pytest.approx(40e-9)
    assert s.idle_share() == pytest.approx(0.6)
    # the step's other custom calls are not the kernel
    assert s.kernel_seconds(["paged_attention_pallas"]) == (
        pytest.approx(25e-9), 1)
    b = s.breakdown()
    assert b["device_ops"][0] == ["paged_attention_pallas.9",
                                  pytest.approx(25e-9)]
    # gaps: 0-10 (bench.step covers 5), 40-60 (midpoint 50: bench.sleep),
    # 70-100 (outside any bench span)
    names = {n for n, _ in b["idle_gaps"]}
    assert names == {"bench.step", "bench.sleep", "outside any bench span"}
    assert sum(d for _, d in b["idle_gaps"]) == pytest.approx(60e-9)


def test_fixture_trace():
    from jax.profiler import ProfileData
    with open(FIXTURE) as f:
        s = tracing.reduce_profile(ProfileData.from_text_proto(f.read()),
                                   chips=1)
    assert s.window_s == pytest.approx(40e-6)
    assert s.busy_s == pytest.approx(24e-6)        # 3 x (2 + 5 + 1) us
    assert s.idle_share() == pytest.approx(0.4)
    # the transposes' statistics name the wrapper; only the kernel's own
    # name counts
    assert s.kernel_seconds(["paged_attention_pallas"]) == (
        pytest.approx(15e-6), 3)
    b = s.breakdown()
    assert b["device_ops"] == [
        ["paged_attention_pallas.9", pytest.approx(15e-6)],
        ["copy_bitcast_fusion.4", pytest.approx(6e-6)],
        ["fusion.9", pytest.approx(3e-6)]]
    # the longest gaps are the three 4.5 us ones between steps, while the
    # host hands out the next requests
    assert [n for n, _ in b["idle_gaps"][:3]] == ["bench.clients"] * 3
    assert sum(d for _, d in b["idle_gaps"]) == pytest.approx(16e-6)


def _fixture_run(traced_steps):
    import types
    from jax.profiler import ProfileData
    with open(FIXTURE) as f:
        ts = tracing.reduce_profile(ProfileData.from_text_proto(f.read()),
                                    chips=1)
    cfg = {"hidden_size": 2048, "num_attention_heads": 16,
           "num_key_value_heads": 8, "num_hidden_layers": 1}
    return types.SimpleNamespace(
        trace_summary=ts, cell=types.SimpleNamespace(config=cfg),
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite")],
        host={"traced_steps": traced_steps})


def test_paged_attn_roofline_counts_one_call_per_layer_and_step():
    from harness.core import BenchError, load_module
    from harness.flops import least_time, paged_attn_cost
    from peaks import peaks_of
    mod = load_module(os.path.join(BENCH, "metrics", "paged_attn_roofline.py"),
                      "bench_metric_paged_attn_roofline")
    steps = [(0, 1, [100] * 8)] * 3
    run = _fixture_run(steps)
    need = 3 * least_time(*paged_attn_cost(run.cell.config, [100] * 8),
                          peaks_of("TPU v5 lite"))
    assert mod.read(run) == pytest.approx(100 * need / 15e-6)
    with pytest.raises(BenchError):
        mod.read(_fixture_run(steps[:2]))

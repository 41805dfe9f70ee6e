"""Whole runs of tiny cells on the CPU: the result line, and `correct`."""
from conftest import run_tiny


def test_sim_run_is_correct(tiny_bench):
    out = run_tiny(tiny_bench, "t.sim")
    assert out["correct"], out["compared"]
    assert out["compared"]["counter_mismatches"]["value"] == 0
    assert set(out["metrics"]) == {"setup_s", "sim_accesses_per_s"}
    assert out["attempted"] > 0 and out["failed"] == 0


def test_serve_run_is_correct(tiny_bench):
    out = run_tiny(tiny_bench, "t.serve", seconds=2.0)
    assert out["correct"], out["compared"]
    assert set(out["metrics"]) == {"setup_s", "decode_tok_s", "itl_p95_ms",
                                   "ttft_p50_s"}

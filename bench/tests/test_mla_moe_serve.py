"""The DeepSeek-V2 serving system on the CPU at a tiny size: a whole run
is correct and reports the cell's metrics, its float8 control fails the
limit the program passes, and a planted fault is caught.

The tiny benchmark holds one cell, ``t.dsv2``, laid out as ``bench/`` is
with the real system, metric readers and the real cell's metric entries.
"""
import json
import os

from conftest import BENCH, ROOT, run_tiny

CELL = "serve.dsv2.reason64"
TINY_DSV2 = {
    "name": "tiny-dsv2", "system": "mla_moe_serve",
    "hidden_act": "silu", "attention_bias": False,
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
    "routed_scaling_factor": 4, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "beta_fast": 32, "beta_slow": 1},
    "torch_dtype": "float32",
    "published": {"num_hidden_layers": 60, "n_routed_experts": 16},
    "deployment": {"first_expert_held": 4},
    "serving": {"max_batch": 4, "max_len": 96, "page_size": 8,
                "block_table": "paged_flat", "decoding": "greedy"},
}
TINY_REASON = {"name": "t.reason", "generator": "chat", "clients": 4,
               "prompt": {"median": 6, "sigma": 0.4},
               "output": {"median": 5, "sigma": 0.4},
               "quantiles": 4, "pair_stride": 3, "correctness_sample": 8}


def make_bench(tmp_path, **widths):
    """A benchmark directory with the one tiny cell ``t.dsv2``."""
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    os.symlink(os.path.join(BENCH, "systems"), bench / "systems")
    for f in os.listdir(os.path.join(BENCH, "metrics")):
        os.symlink(os.path.join(BENCH, "metrics", f), bench / "metrics" / f)
    (bench / "configs" / "tiny-dsv2.json").write_text(
        json.dumps(dict(TINY_DSV2, **widths)))
    (bench / "traffic" / "t.reason.json").write_text(json.dumps(TINY_REASON))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)

    def ours(metrics):
        return [dict(m, workloads=["t.dsv2"]) for m in metrics
                if CELL in m.get("workloads", [CELL])]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "tiny-dsv2",
                     "file": "bench/configs/tiny-dsv2.json"}],
        "workloads": [{"name": "t.dsv2", "config": "tiny-dsv2",
                       "traffic": "t.reason", "chips": 1}],
        "end_to_end": ours(real["end_to_end"]),
        "per_layer": ours(real["per_layer"])}))
    return bench


def test_run_is_correct_and_reports_the_cells_metrics(tmp_path):
    out = run_tiny(make_bench(tmp_path), "t.dsv2", seconds=2.0)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"setup_s", "decode_tok_s", "itl_p95_ms"}
    assert out["compared"]["served_gap_p95"]["value"] < 1e-3
    assert out["compared"]["served_gaps_over_2"]["value"] == 0


def test_traced_run_reads_the_counter(tmp_path, monkeypatch):
    import peaks
    # the CPU has no published peak; any stands in for the arithmetic
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    out = run_tiny(make_bench(tmp_path), "t.dsv2", seconds=2.0, trace=True)
    assert out["correct"], out["compared"]
    # no device trace on the CPU: the kernel's roofline and the scope's
    # device time have nothing to read; the counter-based MFU has
    assert set(out["metrics"]) == {"dsv2.mfu"}
    assert out["metrics"]["dsv2.mfu"]["value"] > 0


def test_control_fails_and_program_passes(tmp_path):
    import jax
    import calibrate
    from harness import core
    from systems import mla_moe_serve
    # served in bfloat16 as the configuration is, wide enough that the
    # float8 control's gap reaches the range it has at full width
    bench = make_bench(tmp_path, torch_dtype="bfloat16", hidden_size=512,
                       intermediate_size=1024, q_lora_rank=128,
                       kv_lora_rank=128, qk_nope_head_dim=64,
                       qk_rope_head_dim=32, v_head_dim=64,
                       moe_intermediate_size=128)
    c = core.Cell("t.dsv2", bench_dir=str(bench))
    line = calibrate.readings(c, c.system(), 987654321987, 2.0,
                              jax.devices("cpu")[:1], core.CompileCounter(),
                              control=True)
    assert line["correct"], line
    assert line["control_correct"] is False
    assert line["control"]["served_gap_p95"] > mla_moe_serve.GAP_P95_LIMIT
    assert line["program"]["served_gap_p95"] < mla_moe_serve.GAP_P95_LIMIT / 3
    assert line["program"]["served_gaps_over_2"] <= mla_moe_serve.FAR_LIMIT


def test_token_altered_is_caught(tmp_path, monkeypatch):
    from repro.serving import _engine
    real = _engine.ServeEngine._engine_step

    def altered(self):
        finished = real(self)
        for req in finished:
            req.generated[-1] = (req.generated[-1] + 1) % self.cfg.vocab_size
        return finished
    monkeypatch.setattr(_engine.ServeEngine, "_engine_step", altered)
    assert not run_tiny(make_bench(tmp_path), "t.dsv2", seconds=2.0)["correct"]

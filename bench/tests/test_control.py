"""The controls fail the limits that the program's own runs pass.

The control is the plain reference put in the program's place one
precision below the configuration's: bfloat16 cycle arithmetic for the
simulator (float32 stated), float8 e4m3 matmul inputs for serving
(bfloat16 stated), each judged by the cell's own limits through
``calibrate.readings``.  At the cells' own sizes it runs on the chip
through ``bench/calibrate.py --control``; here at small sizes on the CPU.
"""
import json

from conftest import make_tiny_bench


def _control(bench, cell, **kw):
    """calibrate's line for one seed, on the CPU."""
    import jax
    import calibrate
    from harness import core
    c = core.Cell(cell, bench_dir=str(bench))
    return calibrate.readings(c, c.system(), 987654321987,
                              kw.get("seconds", 1.0), jax.devices("cpu")[:1],
                              core.CompileCounter(), control=True)


def test_sim_control_fails_and_program_passes(tmp_path):
    from systems import sim
    bench = make_tiny_bench(tmp_path)
    line = _control(bench, "t.sim")
    assert line["correct"]
    assert line["control_correct"] is False
    assert line["control"]["cycles_rel_max"] > 3 * sim.CYCLES_LIMIT
    assert line["program"]["cycles_rel_max"] < sim.CYCLES_LIMIT / 3


def test_serve_control_fails_and_program_passes(tmp_path):
    from systems import lm_serve
    bench = make_tiny_bench(tmp_path)
    cfg_path = bench / "configs" / "tiny-lm.json"
    cfg = json.loads(cfg_path.read_text())
    # served in bfloat16 as the configuration is, and wide enough (a
    # quarter of its width, a sixth of its depth) that the float8
    # control's gap reaches the full-size reading's range (0.39-0.52 here
    # over three seeds; 0.52-0.60 at full width and 2-8 layers)
    cfg.update(torch_dtype="bfloat16", hidden_size=512,
               intermediate_size=1024, num_hidden_layers=4)
    cfg_path.write_text(json.dumps(cfg))
    line = _control(bench, "t.serve", seconds=2.0)
    assert line["correct"], line
    assert line["control_correct"] is False
    assert line["control"]["served_gap_max"] > lm_serve.GAP_LIMIT
    assert line["program"]["served_gap_max"] < lm_serve.GAP_LIMIT / 3

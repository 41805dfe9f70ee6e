"""A configuration, a mix and a metric added as new files are found by
name, with no edit to a file that is there."""
import json
import textwrap

from conftest import make_tiny_bench, run_tiny, tiny_sim_config, tiny_sim_mix


def test_new_config_mix_and_metric_found_by_name(tmp_path):
    bench = make_tiny_bench(tmp_path)
    (bench / "configs" / "tiny-ndp-3c.json").write_text(
        json.dumps(dict(tiny_sim_config(cores=3), accesses_per_core=512)))
    (bench / "traffic" / "t.short.json").write_text(
        json.dumps(tiny_sim_mix(chunk=256, n=2)))
    (bench / "metrics" / "t.jobs_run.py").write_text(textwrap.dedent('''
        def read(run):
            return float(len(run.host["jobs"]))
    '''))
    path = tmp_path / "BENCHMARK.json"
    b = json.loads(path.read_text())
    b["configs"].append({"name": "tiny-ndp-3c",
                         "file": "bench/configs/tiny-ndp-3c.json"})
    b["workloads"].append({"name": "t.new", "config": "tiny-ndp-3c",
                           "traffic": "t.short", "chips": 1})
    b["per_layer"].append({"name": "t.jobs_run", "unit": "jobs",
                           "workloads": ["t.new"]})
    path.write_text(json.dumps(b))
    out = run_tiny(bench, "t.new", trace=True)
    assert out["correct"]
    assert out["metrics"]["t.jobs_run"]["value"] >= 1
    assert "sim.scan_us_per_step" not in out["metrics"]

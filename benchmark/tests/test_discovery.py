"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric by adding files and one `workloads` entry: nothing that is there is
edited. Shown on a copy of the benchmark's directory with new files dropped in."""

import json
import os
import shutil

from benchmark import cells

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)


def test_dropped_in_files_are_picked_up(tmp_path):
    bench = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(".*", "__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    # the new files
    (bench / "configs" / "new_cfg.json").write_text(json.dumps({
        "name": "new_cfg", "source": "a test", "generator": "new_gen", "reduced": [],
        "params": {}, "data": {"n": 3}}))
    (bench / "generators" / "new_gen.py").write_text("def prepare(cfg, seed, out):\n    return {'n': cfg['data']['n'], 'seed': seed}\n")
    (bench / "traffic" / "new_mix.json").write_text(json.dumps({"kind": "batch_jobs", "argv": ["x"]}))
    (bench / "layer_metrics" / "new.metric.py").write_text("def read(run):\n    return run.get('answer')\n")
    # the entries
    spec["configs"].append({"name": "new_cfg", "source": "a test", "file": "benchmark/configs/new_cfg.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "new_cfg.new_mix", "config": "new_cfg", "traffic": "new_mix",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "new.metric", "unit": "count", "better": "higher",
                              "source": "program_counter", "layer": "test", "moves": "setup_s",
                              "workloads": ["new_cfg.new_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    loaded = cells.load_cell("new_cfg.new_mix", bench_dir=str(bench))
    assert loaded["config"]["data"]["n"] == 3 and loaded["traffic"]["argv"] == ["x"]
    assert loaded["generator"].prepare(loaded["config"], 5, "out") == {"n": 3, "seed": 5}
    got = cells.read_layer_metrics(spec, "new_cfg.new_mix", {"answer": 42}, bench_dir=str(bench))
    assert got == {"new.metric": {"value": 42.0, "unit": "count"}}
    # a reader that finds nothing to read leaves its metric out
    assert cells.read_layer_metrics(spec, "new_cfg.new_mix", {}, bench_dir=str(bench)) == {}
    # and an old cell is found as before, with nothing edited
    assert cells.load_cell(spec["workloads"][0]["name"], bench_dir=str(bench))["cell"]["chips"] in (1, 4)
    assert all(p.read_bytes() == data for p, data in before.items())

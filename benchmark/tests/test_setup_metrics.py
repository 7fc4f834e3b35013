"""The per-layer readers that move `setup_s`: each reads `process.first_job`
of the window's last sound job, the program's own ledger of its process's
first job (in a run of the harness the warm-up job, whose record is deleted
with its work directory), each on a hand-made run."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import cells

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

FIRST = {
    "verb": "compare", "began_at_s": 12.5, "bring_up_s": 0.25, "job_s": 30.0,
    "compile": {"programs": 40, "trace_s": 3.0, "lower_s": 1.5, "backend_compile_s": 16.0,
                "cache_load_s": 2.0, "cache_hits": 30, "cache_misses": 10,
                "by_program": [], "by_span": {}},
}
EXPECT = {
    "setup_pre_job_s": 12.5, "setup_first_job_s": 30.25, "setup_first_job_cold_s": 30.0 - 9.0,
    "setup_trace_lower_s": 4.5, "setup_backend_compile_s": 16.0, "setup_cache_load_s": 2.0,
    "setup_programs": 40, "setup_cache_hit_share": 75.0,
}
UNITS = {"setup_programs": ("count", "lower"), "setup_cache_hit_share": ("%", "higher")}
LAYERS = {name: "workflow" if name in ("setup_pre_job_s", "setup_first_job_s", "setup_first_job_cold_s")
          else "device" for name in EXPECT}


def _reader(name):
    return cells.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


def _job(job_seconds: float, first_job) -> dict:
    rec = {"stages": {}, "phases": {"job": {"seconds": job_seconds, "self_seconds": 0.5, "calls": 1,
                                            "thread": "main"}}}
    if first_job is not None:
        rec["process"] = {"clock": "proc_stat", "imported_at_s": 0.5, "began_at_s": 50.0,
                          "bring_up_s": 0.001, "n_jobs": 3, "first_job": first_job, "jobs": []}
    return {"wall_s": job_seconds + 0.1, "record": rec}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_reads_the_first_job_of_the_window_s_last_record(name):
    reader = _reader(name)
    # an earlier record that says otherwise is not read: the ledger is the process's, and the
    # last sound job carries all of it; the window's `job` spans are 8, 9 and 10 s
    stale = {**FIRST, "began_at_s": 1.0, "job_s": 1.0, "bring_up_s": 1.0,
             "compile": dict.fromkeys(FIRST["compile"], 1)}
    run = {"jobs": [_job(8.0, stale), _job(10.0, stale), _job(9.0, FIRST)]}
    assert reader.read(run) == pytest.approx(EXPECT[name])
    # the parent of the PR that brought the ledger has no `process`: nothing to read
    assert reader.read({"jobs": [_job(9.0, None)]}) is None
    assert reader.read({"jobs": [{"wall_s": 9.0, "record": {"stages": {}}}]}) is None
    assert reader.read({"jobs": []}) is None and reader.read({}) is None


def test_the_hit_share_is_none_where_no_request_used_the_cache():
    off = {**FIRST, "compile": {**FIRST["compile"], "cache_hits": 0, "cache_misses": 0}}
    assert _reader("setup_cache_hit_share").read({"jobs": [_job(9.0, off)]}) is None
    cold = {**FIRST, "compile": {**FIRST["compile"], "cache_hits": 0, "cache_misses": 40}}
    assert _reader("setup_cache_hit_share").read({"jobs": [_job(9.0, cold)]}) == 0.0


def test_pre_job_and_first_job_add_up_to_the_warm_up_s_end():
    run = {"jobs": [_job(9.0, FIRST)]}
    total = _reader("setup_pre_job_s").read(run) + _reader("setup_first_job_s").read(run)
    assert total == FIRST["began_at_s"] + FIRST["bring_up_s"] + FIRST["job_s"]


def test_every_setup_metric_is_declared_with_its_reader_and_the_six_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    declared = {m["name"]: m for m in spec["per_layer"]}
    names = [m["name"] for m in spec["per_layer"]]
    assert set(EXPECT) <= set(declared)
    six = ["mags_5k.compare_dense", "mags_5k.primary_stream", "gtdb_reps_10k.primary_ring4",
           "ecoli_1k.secondary_deep", "mag_fasta_384.dereplicate", "gtdb_release_6k.compare_greedy"]
    by_name = {w["name"] for w in spec["workloads"]}
    for name in EXPECT:
        m = declared[name]
        assert m["moves"] == "setup_s" and m["source"] == "program_counter"
        assert (m["unit"], m["better"]) == UNITS.get(name, ("s", "lower")), name
        assert m["layer"] == LAYERS[name]
        # wherever later cells are appended
        assert m["workloads"][:6] == six and set(m["workloads"]) <= by_name, name
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    # appended after the metrics that were there, which all move `job_wall_s`
    first = min(names.index(n) for n in EXPECT)
    assert all(m["moves"] == "job_wall_s" for m in spec["per_layer"][:first])
    assert any(m["name"] == "setup_s" and "workloads" not in m for m in spec["end_to_end"])


def test_a_rehearsed_traced_line_holds_all_eight():
    argv = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "mags_5k.primary_stream",
            "--seed", str(2**31 + 36), "--seconds", "4", "--trace", "1", "--rehearse"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(argv, capture_output=True, text=True, env=env, timeout=900, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True and line["failed"] == 0
    got = {name: line["metrics"][name] for name in EXPECT}  # KeyError names the one left out
    assert all(got[name]["unit"] == UNITS.get(name, ("s",))[0] for name in EXPECT)
    # the harness's own lines agree with the program's ledger
    planted = float(proc.stdout.split("sketch sets at ")[1].split("s")[0])
    took = float(proc.stdout.split("warm-up job took ")[1].split("s")[0])
    assert planted <= got["setup_pre_job_s"]["value"] < planted + 5.0  # the controller's import
    assert got["setup_first_job_s"]["value"] == pytest.approx(took, abs=0.3)
    assert got["setup_programs"]["value"] >= 1 and line["metrics"]["compiles_in_window.batch"]["value"] == 0
    assert 0.0 <= got["setup_first_job_cold_s"]["value"] < got["setup_first_job_s"]["value"]
    assert got["setup_trace_lower_s"]["value"] > 0.0

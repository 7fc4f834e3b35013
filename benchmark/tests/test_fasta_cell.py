"""The cell `mag_fasta_384.dereplicate`: its files are found by name, its
generator is a function of the seed, its readers read a job's record and give
nothing on a record without the spans, a job whose record names the NumPy
ingest counts as failed, its control fails a limit and leaves the exact
comparisons alone, its margin sweep runs at toy size, and a rehearsal of the
whole cell prints a well-formed line, sound and with a winner altered where it
is produced."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cells, control_fasta, fasta_jobs, margin_sweep_fasta
from benchmark import reference_fasta as rf

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "mag_fasta_384.dereplicate"
NEW = ["filter_s", "ingest_s", "ingest_sketch_mb_per_core_s", "ingest_pool_busy_share", "choose_s",
       "evaluate_s"]
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _reader(name):
    return cells.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


def _toy():
    cfg = cells.read_json(os.path.join(BENCH, "configs", "mag_fasta_384.json"))
    cfg["data"].update(cfg["rehearse"])
    return cfg, cells.load_module(os.path.join(BENCH, "generators", cfg["generator"] + ".py"))


def _record(**over):
    """A job's record as a `dereplicate` from FASTA leaves it (the keys the readers read)."""
    phases = {"job": (17.0, 0.1), "stage:filter": (7.0, 0.01), "filter/fasta_stats": (6.9, 6.9),
              "stage:ingest_or_cache": (5.0, 0.05), "ingest/sketch": (4.5, 3.5),
              "ingest/pool_start": (0.8, 0.8), "ingest/shard_flush": (0.2, 0.2),
              "ingest/cache_save": (0.45, 0.45), "stage:choose": (0.6, 0.01),
              "stage:evaluate": (0.2, 0.01)}
    rec = {"platform": "tpu", "device_kind": "TPU v5 lite", "n_devices": 1,
           "stages": {"ingest_or_cache": {"seconds": 5.0}},
           "secondary_paths": {"one_shot_clusterlocal": 3}, "notes": {"ingest_path": "native"},
           "ingest": {"genomes": 330, "file_bytes": 9 * 10**8, "bases": 891_000_000,
                      "valid_kmers": 890_000_000, "bottom_hashes": 330_000, "scaled_hashes": 4_400_000,
                      "busy_seconds": 19.8, "workers": 6, "path": "native"},
           "phases": {k: {"seconds": s, "self_seconds": own, "calls": 1, "thread": "main"}
                      for k, (s, own) in phases.items()}}
    rec.update(over)
    return rec


def test_the_cell_is_found_by_name_and_declared_where_it_reports():
    loaded = cells.load_cell(CELL)
    assert loaded["cell"] == {**loaded["cell"], "config": "mag_fasta_384", "traffic": "dereplicate",
                              "chips": 1}
    assert loaded["traffic"]["kind"] == "fasta_jobs" and hasattr(fasta_jobs, "run")
    assert hasattr(loaded["generator"], "prepare") and hasattr(loaded["generator"], "plan")
    cfg, mix = loaded["config"], loaded["traffic"]
    assert cfg["data"]["n"] == 384 and cfg["reduced"] == ["n"] and cfg["rehearse"]["n"] == 24
    assert cfg["params"]["processes"] == 6 and "-p" not in mix["argv"]  # the program's default
    assert mix["argv"][:2] == ["dereplicate", "{workdir}"] and "--genomeInfo" in mix["argv"]
    assert mix["expect"] == {"secondary_path": "one_shot_clusterlocal", "ingest_path": "native"}
    # the dense cell's limits for the pair values; the score's between them and the control
    dense = cells.read_json(os.path.join(BENCH, "traffic", "compare_dense.json"))["limits"]
    assert {k: mix["limits"][k] for k in dense} == dense and 1e-7 < mix["limits"]["score"] < 1e-4
    # the thresholds are those of the other deployments, word for word, and upstream's filters
    shared = cells.read_json(os.path.join(BENCH, "configs", "mags_5k.json"))["params"]
    assert {k: cfg["params"][k] for k in shared} == shared
    assert (cfg["params"]["length"], cfg["params"]["completeness"], cfg["params"]["contamination"]) \
        == (50000, 75, 25)
    spec = loaded["spec"]
    assert [m["name"] for m in cells.metrics_of(spec, CELL, "end_to_end")] == ["setup_s", "job_wall_s"]
    mine = {m["name"] for m in cells.metrics_of(spec, CELL, "per_layer")}
    assert set(NEW) <= mine and {"secondary_useful_pair_share", "mash_kernel_ns_per_pair",
                                 "idle_attributed", "tables_s"} <= mine
    assert not {"load_sketches_s", "ring_collective_exposed", "secondary_chunked_roofline"} & mine
    assert [m["name"] for m in spec["per_layer"][-len(NEW):]] == NEW
    for m in spec["per_layer"][-len(NEW):]:
        assert m["workloads"] == [CELL] and m["moves"] == "job_wall_s"
    # appended, never put first: the cells that were there keep their places
    for m in spec["per_layer"] + spec["end_to_end"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL
    assert spec["workloads"][-1]["name"] == CELL and spec["configs"][-1]["name"] == "mag_fasta_384"
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1


def test_the_generator_is_a_function_of_the_seed(tmp_path):
    cfg, gen = _toy()
    assert gen.plan(cfg["data"], 2**31 + 7) == gen.plan(cfg["data"], 2**31 + 7)
    assert gen.plan(cfg["data"], 2**31 + 7) != gen.plan(cfg["data"], 2**31 + 8)

    def digest(out_dir, seed):
        data = gen.generate(cfg["data"], seed, str(out_dir))
        h = hashlib.sha256(open(data.genome_info, "rb").read())
        for p in data.paths:
            h.update(open(p, "rb").read())
        return h.hexdigest(), data

    first, data = digest(tmp_path / "a", 2**31 + 7)
    assert first == digest(tmp_path / "b", 2**31 + 7)[0] != digest(tmp_path / "c", 5)[0]
    assert len(data.names) == 24 == len(set(data.names)) and data.names == sorted(data.names)
    # every seed is the same work: the files hold the stated bases, to a few percent
    assert data.bases.sum() == pytest.approx(cfg["data"]["total_bases"], rel=0.08)
    # the full-size plan: 384 genomes, 8 short bins, the stated total, roots of 1-3 groups
    full = cells.read_json(os.path.join(BENCH, "configs", "mag_fasta_384.json"))["data"]
    for seed in (1, 3000000555):
        tasks = gen.plan(full, seed)
        members = [(t, m) for t in tasks for g in t["groups"] for m in g["members"]]
        assert sorted(m["index"] for _, m in members) == list(range(384))
        assert sum(bool(m.get("short")) for _, m in members) == 8
        planned = sum(t["length"] * (m["completeness"] + m["contamination"]) for t, m in members)
        assert planned == pytest.approx(1.04e9, rel=0.02)
        assert {len(t["groups"]) for t in tasks} == {1, 2, 3}
        assert all(400_000 <= t["length"] <= 9_000_000 for t, m in members if not m.get("short"))


def test_the_readers_on_a_record_of_the_cell():
    rec = _record()
    run = {"jobs": [{"wall_s": 17.0, "record": rec}, {"wall_s": 18.0, "record": rec}]}
    assert _reader("filter_s").read(run) == 7.0 and _reader("choose_s").read(run) == 0.6
    assert _reader("evaluate_s").read(run) == 0.2 and _reader("ingest_s").read(run) == 5.0
    assert _reader("ingest_sketch_mb_per_core_s").read(run) == pytest.approx(891.0 / 19.8)
    assert _reader("ingest_pool_busy_share").read(run) == pytest.approx(100 * 19.8 / (6 * 4.5))
    # load_sketches_s reads the stage's self seconds: in such a job, what the spans leave
    assert _reader("load_sketches_s").read(run) == 0.05
    # a share of the pool's capacity cannot pass 100%: the workers' seconds lie inside the span
    assert _reader("ingest_pool_busy_share").read(run) < 100


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_and_the_counters_gives_the_readers_nothing(name):
    """The parent of this PR: stage spans and stage totals, no `ingest` counter, no
    spans inside the stages. `dereplicate` stage spans were there already."""
    parent = _record()
    del parent["ingest"]
    parent["phases"] = {k: v for k, v in parent["phases"].items() if "/" not in k}
    run = {"jobs": [{"wall_s": 17.0, "record": parent}]}
    value = _reader(name).read(run)
    if name in ("filter_s", "choose_s", "evaluate_s"):
        assert value == parent["phases"]["stage:" + name[:-2]]["seconds"]
    else:
        assert value is None
    bare = {"jobs": [{"wall_s": 1.0, "record": {"stages": {}}}]}
    assert _reader(name).read(bare) is None
    assert _reader(name).read({"jobs": []}) is None and _reader(name).read({}) is None


@pytest.mark.parametrize("record,kept,fault", [
    (_record(), 330, None),
    (_record(notes={"ingest_path": "numpy"}), 330, "ingest_path='numpy'"),
    (_record(notes={}), 330, "ingest_path=None"),
    (_record(secondary_paths={"cpu_tiles": 2}), 330, "secondary"),
    (_record(secondary_paths={"matmul_chunked": 1}), 330, "one_shot_clusterlocal"),
    (_record(), 331, "sketched 330 of the 331"),
    (_record(platform="cpu"), 330, "platform"),
    (_record(fault_tolerance={"retries": 1}), 330, "retries"),
])
def test_a_job_that_did_not_run_as_the_cell_means_counts_as_failed(record, kept, fault):
    expect = cells.load_cell(CELL)["traffic"]["expect"]
    faults = fasta_jobs.job_faults(record, DEVICE, expect, "sort", kept)
    assert (faults == []) if fault is None else any(fault in f for f in faults), faults


def test_the_argv_names_every_planted_file_and_a_workdir_that_does_not_exist(tmp_path):
    cfg, gen = _toy()
    data = gen.generate(cfg["data"], 9, str(tmp_path / "planted"))
    mix = cells.load_cell(CELL)["traffic"]
    argv = fasta_jobs.job_argv(mix["argv"], str(tmp_path / "job0"), data)
    assert argv[:3] == ["dereplicate", str(tmp_path / "job0"), "-g"] and argv[3:27] == data.paths
    assert argv[27:] == ["--genomeInfo", data.genome_info, "--skip_plots"]
    assert all(os.path.isfile(p) for p in data.paths) and not os.path.exists(argv[1])
    quality = fasta_jobs.read_quality(data.genome_info)
    assert [quality[g]["completeness"] for g in data.names] == list(data.completeness)
    assert [quality[g]["contamination"] for g in data.names] == list(data.contamination)


def test_reference_sketch_by_hand_on_a_few_lines(tmp_path):
    """Which characters make a k-mer invalid, and what a contig is."""
    path = tmp_path / "tiny.fa"
    path.write_bytes(b">a desc\nACGTACGTAC\nGTNACGTTTGA\n\n>empty\n>b\n  acgtRacgtac  \n")
    assert rf.read_contigs(str(path)) == [b"ACGTACGTACGTNACGTTTGA", b"acgtRacgtac"]
    s = rf.sketch_file(str(path), 4, 1000, 1)
    assert (s["length"], s["contigs"], s["N50"]) == (32, 2, 21)
    # windows of 4: 9 before the N, 5 after it; 1 before the R, 3 after it; none across contigs
    assert s["valid_kmers"] == 9 + 5 + 1 + 3
    canon = {min(w, "".join("TGCA"["ACGT".index(c)] for c in reversed(w)))
             for w in ["ACGT", "CGTA", "GTAC", "TACG", "CGTT", "GTTT", "TTTG", "TTGA"]}
    assert len(s["scaled"]) == len(canon) == len(s["bottom"])  # scale 1 keeps every distinct hash
    packed = np.array(sorted(sum("ACGT".index(c) << 2 * (3 - i) for i, c in enumerate(w)) for w in canon),
                      np.uint64)
    assert np.array_equal(np.sort(rf.splitmix64(packed)), s["scaled"])
    assert rf.n50([5, 3, 2]) == 5 and rf.n50([3, 3, 3, 3]) == 3 and rf.n50([]) == 0


def test_margin_sweep_and_control_at_toy_size(capsys):
    cfg, gen = _toy()
    row = margin_sweep_fasta.sweep(cfg, gen, 12)
    assert row["kept"] >= 4 and row["scaled_max"] < 1000 and np.isfinite(row["primary_cut_gap"])
    assert row["completeness_gap"] >= 0 and row["length_gap"] >= 0
    # the control fails the ANI, the Mash and the score limit and leaves the exact ones alone
    assert control_fasta.main(["--workload", CELL, "--seeds", "12", "--rehearse"]) == 0
    printed = capsys.readouterr().out
    wrong = [line for line in printed.splitlines() if line.endswith("WRONG")]
    assert "correct = False" in printed and 2 <= len(wrong) <= 3
    assert any("score error" in line for line in wrong) and any("ANI error" in line for line in wrong)
    mix = cells.load_cell(CELL)["traffic"]
    out = control_fasta.control(cfg, mix, gen, 12)
    assert all(c["ok"] for c in out if c["limit"] == 0) and all(np.isfinite(c["value"]) for c in out)


DRIVER = r"""
import sys
sys.path.insert(0, {repo!r})
{patch}
sys.argv = ["run.py"] + {argv!r}
import runpy
runpy.run_path({repo!r} + "/benchmark/run.py", run_name="__main__")
"""

# the first cluster of two or more gives its lowest score as its winner
BREAK_CHOOSE = r"""
import drep_tpu.choose as ch
_real = ch.pick_winners
def _broken(sdb_full):
    sizes = sdb_full.groupby("secondary_cluster")["genome"].transform("size")
    worst = sdb_full[sizes > 1].sort_values(["secondary_cluster", "score"]).index[0]
    flipped = sdb_full.copy()
    flipped.loc[worst, "score"] += 1000.0
    out = _real(flipped)
    out["score"] = out["genome"].map(sdb_full.set_index("genome")["score"]).to_numpy()
    return out
ch.pick_winners = _broken
"""


def _run(patch: str, trace: int) -> tuple[dict, str]:
    argv = ["--workload", CELL, "--seed", str(2**31 + 11), "--seconds", "6", "--trace", str(trace),
            "--rehearse"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, "-c", DRIVER.format(repo=REPO, patch=patch, argv=argv)],
                          capture_output=True, text=True, env=env, timeout=900, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def test_a_rehearsal_prints_a_well_formed_line_with_every_new_metric():
    line, out = _run("", trace=1)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert set(NEW) <= set(line["metrics"]) and "load_sketches_s" not in line["metrics"]
    assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
    assert 0 < line["metrics"]["ingest_pool_busy_share"]["value"] <= 100
    assert line["metrics"]["ingest_sketch_mb_per_core_s"]["unit"] == "Mb/s"
    gaps = [label for label, seconds in line["breakdown"]["idle_gaps"] if seconds > 1e-3]
    assert gaps and all(label.startswith("host:drep:") for label in gaps), gaps  # never unattributed
    assert out.count("compare: ") == 13 and "WRONG" not in out and "note: planted to pass" in out
    assert not os.path.exists(os.path.join(BENCH, ".work", f"{CELL}-{2**31 + 11}"))


def test_a_winner_altered_where_it_is_produced_is_not_correct():
    line, out = _run(BREAK_CHOOSE, trace=0)
    assert line["correct"] is False and line["failed"] == 0  # a wrong answer is not a failed job
    wrong = [ln for ln in out.splitlines() if ln.endswith("WRONG")]
    assert len(wrong) == 1 and "winners" in wrong[0]
    assert set(line["metrics"]) == {"setup_s", "job_wall_s"}

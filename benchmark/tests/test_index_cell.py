"""The cell `gtdb_index_6k.update_admit`: its files are found by name, its
table without the batch is `gtdb_release_6k`'s and its shapes are that file's
word for word, the layout takes no seed, the program's state after `index
build` + `index update` equals the from-scratch reference on three seeds, the
controls come out as not correct, and the whole cell rehearses."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cells, check, control_index, index_jobs, margin_sweep_index
from benchmark import reference_index as ri

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL, CONFIG = "gtdb_index_6k.update_admit", "gtdb_index_6k"
NEW = ["index_load_s", "index_rect_s", "index_partition_s", "index_secondary_s", "index_score_s",
       "index_publish_s", "index_recomputed_member_share", "index_published_bytes_per_genome"]
SHAPES = ("s_bottom", "s_scaled", "kmer_size", "scale", "hash", "genome_length", "layout_seed",
          "lineage_size", "strain_size", "ani_edge", "accessory_max")


@pytest.fixture(scope="module")
def loaded():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def toy(loaded):
    cfg = loaded["config"]
    return {**cfg, "data": {**cfg["data"], **cfg["rehearse"]}}


def test_the_cell_is_found_by_name_and_declared_where_it_reports(loaded):
    assert loaded["cell"] == {**loaded["cell"], "config": CONFIG, "traffic": "update_admit", "chips": 1}
    mix, cfg, spec = loaded["traffic"], loaded["config"], loaded["spec"]
    assert mix["kind"] == "index_jobs" and hasattr(index_jobs, "run")
    assert mix["argv"] == ["index", "update", "{index}", "--params_file", "{batch}", "-p", "6"]
    assert [a[:2] for a in mix["setup_argv"]] == [["compare", "{workdir}"], ["index", "build"]]
    assert "--greedy_secondary_clustering" not in mix["setup_argv"][0]
    assert cfg["reduced"] == ["n"] == list(cfg["reduced_why"]) and len(cfg["guarantees"]) == 4
    assert [g[:3] for g in cfg["guarantees"]] == ["(a)", "(b)", "(c)", "(d)"]
    assert [m["name"] for m in cells.metrics_of(spec, CELL, "end_to_end")] == ["setup_s", "job_wall_s"]
    # the eight metrics of the index layer are the last eight, and this cell's alone
    assert [m["name"] for m in spec["per_layer"][-8:]] == NEW
    for m in spec["per_layer"][-8:]:
        assert m["workloads"] == [CELL] and m["moves"] == "job_wall_s" and m["layer"] == "index"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    mine = {m["name"] for m in cells.metrics_of(spec, CELL, "per_layer")}
    assert {"host_unattributed_s", "idle_attributed", "device_idle.batch", "compiles_in_window.batch",
            "primary_device_wait_s", "secondary_device_wait_s", "setup_first_job_s"} <= mine
    # their readers find nothing in an index job: the rectangle's pairs are booked under the index's
    # own stage, a one-shot call opens the chunked call's span, and no table is written
    assert not {"mash_kernel_ns_per_pair", "secondary_chunked_roofline", "primary_stage_s",
                "secondary_stage_s", "tables_s", "load_sketches_s"} & mine
    # appended, never put first: the cells that were there keep their places
    assert spec["workloads"][-1]["name"] == CELL and spec["configs"][-1]["name"] == CONFIG
    for m in spec["per_layer"] + spec["end_to_end"]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL
    assert len(spec["workloads"]) == 10 and sum(w["chips"] == 4 for w in spec["workloads"]) == 3


def test_the_shapes_are_the_releases_word_for_word_and_the_index_is_its_table(loaded):
    cfg, gen = loaded["config"], loaded["generator"]
    release = cells.read_json(os.path.join(BENCH, "configs", "gtdb_release_6k.json"))
    assert {k: cfg["data"][k] for k in SHAPES} == {k: release["data"][k] for k in SHAPES}
    assert {k: v for k, v in cfg["params"].items() if k in release["params"]} == release["params"]
    assert cfg["params"]["streaming_block"] == 1024 and cfg["params"]["processes"] == 6
    assert gen.old_table(cfg["data"]) == release["data"]["clusters"]
    assert cfg["data"]["n"] == release["data"]["n"] + cfg["data"]["k_batch"] == 6144 + 256
    new = sum(sum(e.get("new", [])) * e["count"] for e in cfg["data"]["clusters"])
    assert new == cfg["data"]["k_batch"]
    toy = {**cfg["data"], **cfg["rehearse"]}
    assert toy["n"] == 96 + 16 and toy["k_batch"] == 16
    assert sum(e["size"] * e["count"] for e in gen.old_table(toy)) == 96
    # who joins: 69% of the batch an existing cluster, 80 of 256 found one (32 in 12 clusters, 48 alone)
    founds = sum(sum(e["new"]) * e["count"] for e in cfg["data"]["clusters"]
                 if e.get("new") and sum(e["new"]) == e["size"])
    assert founds == 80 and new - founds == 176


def test_the_seed_draws_hash_values_and_nothing_else(loaded, toy):
    gen = loaded["generator"]
    a, b = gen.generate(toy["data"], 5), gen.generate(toy["data"], 2**31 + 6)
    assert a.n_old == b.n_old == 96 and a.names == b.names
    for field in ("primary_labels", "labels", "length", "n_kmers"):
        assert getattr(a.union, field).tobytes() == getattr(b.union, field).tobytes()
    assert a.is_new.tobytes() == b.is_new.tobytes() and int(a.is_new.sum()) == 16
    assert [len(s) for s in a.union.scaled] == [len(s) for s in b.union.scaled]
    assert not np.array_equal(a.union.scaled[0], b.union.scaled[0])
    rel = gen._release()
    laid = rel.plan(toy["data"])
    assert laid.slot_table() == rel.plan(toy["data"]).slot_table()
    assert gen.batch_slots(toy["data"], laid).tobytes() == gen.batch_slots(toy["data"], laid).tobytes()
    # the index's genomes come first, in the release's scattered order
    assert a.old().names == a.names[:96] and len(set(a.names)) == 112


def _ctx(loaded, toy, seed, work_dir):
    import time

    t0 = time.monotonic()
    return {"config": toy, "traffic": loaded["traffic"], "generator": loaded["generator"], "seed": seed,
            "work_dir": work_dir, "setup_clock": lambda: time.monotonic() - t0}


@pytest.fixture(scope="module")
def updated(loaded, toy, tmp_path_factory):
    """`compare`, `index build` and one `index update` through the CLI's own
    function at toy size, three seeds: (data, job) each."""
    out = []
    for seed in (3, 2**31 + 11, 17):
        work = str(tmp_path_factory.mktemp(f"index_cell_{seed}"))
        made = index_jobs.set_up(_ctx(loaded, toy, seed, work))
        job = index_jobs.run_job(loaded["traffic"]["argv"], made["pristine"], os.path.join(work, "job0"),
                                 made["batch"])
        assert job["error"] is None, job["error"]
        index_jobs._read_record(job)
        out.append((made, job))
    return out


def test_the_state_after_build_and_update_is_the_from_scratch_clustering(loaded, toy, updated, capsys):
    mix = loaded["traffic"]
    for made, job in updated:
        data = made["data"]
        assert index_jobs.index_faults(job["record"], 16) == []
        got = index_jobs.read_answers(job["index"], data.names)
        want = index_jobs.reference_of(data, toy["params"])
        out = index_jobs.check_index(got, job["record"]["index"], data, toy["params"], mix["compare"],
                                     mix["limits"], want)
        out += index_jobs.check_guarantees(got, None, made["digests"],
                                           index_jobs.payload_digests(job["index"]), 112, 96)
        assert check.report(out) and len(out) == 18
        assert sum(c["limit"] == 0 for c in out) == 16
        assert want["work"] == {"clusters_recomputed": 10, "clusters_reused": 24, "members_recomputed": 78,
                                "secondary_calls": 8, "singletons_scored": 2}
        assert index_jobs.state_digest(job["index"]) == index_jobs.state_digest(job["index"])
    assert "WRONG" not in capsys.readouterr().out
    # an update appends: the pristine index is still generation 0, byte for byte
    made, job = updated[0]
    assert index_jobs.payload_digests(made["pristine"]) == made["digests"]
    with open(os.path.join(made["pristine"], "manifest.json")) as f:
        assert json.load(f)["generation"] == 0


def test_a_store_that_kept_one_dirty_cluster_or_lost_a_payload_is_not_correct(loaded, toy, updated):
    mix = loaded["traffic"]
    made, job = updated[0]
    data = made["data"]
    got = index_jobs.read_answers(job["index"], data.names)
    want = index_jobs.reference_of(data, toy["params"])
    want["old_primary"] = ri.rg.primary(data.union.bottom[:96], 1000, 21, 0.1)[0]
    kept, short = control_index.keep_generation0(got, job["record"]["index"], want, 96)
    out = index_jobs.check_index(kept, short, data, toy["params"], mix["compare"], mix["limits"], want)
    wrong = [c["what"] for c in out if not c["ok"]]
    assert any("primary cluster the reference does not have" in w for w in wrong)
    assert any("index.clusters_recomputed" in w for w in wrong) and len(wrong) >= 5
    # a moved score, a dropped edge and a winner that is not its cluster's best are each caught
    moved = {**got, "score": got["score"] + 1e-4}
    assert [c["ok"] for c in index_jobs.check_index(moved, {}, data, toy["params"], ["score"],
                                                    mix["limits"], want)] == [False]
    e = got["edges"]
    new = np.flatnonzero(np.maximum(e["i"], e["j"]) >= 96)
    fewer = {**got, "edges": {k: np.delete(v, new[0]) for k, v in e.items()}}
    assert not index_jobs.check_index(fewer, {}, data, toy["params"], ["edges"], mix["limits"], want)[0]["ok"]
    group = np.flatnonzero(want["secondary"] == want["secondary"][got["winners"][0]])
    worst = group[np.argmin(want["score"][group])]
    if want["score"][worst] < want["score"][group].max() - 1e-4:
        swapped = {**got, "winners": np.sort(np.append(got["winners"][1:], worst))}
        assert not index_jobs.check_index(swapped, {}, data, toy["params"], ["winners"],
                                          mix["limits"], want)[0]["ok"]
    # guarantees: a generation-0 payload that moved, a store that does not read back
    before = dict(made["digests"])
    first = sorted(before)[0]
    bad = index_jobs.check_guarantees(got, None, {**before, first: "0" * 64},
                                      index_jobs.payload_digests(job["index"]), 112, 96)
    assert [c["ok"] for c in bad] == [True, True, False]
    unread = index_jobs.check_guarantees(None, "CorruptPayloadError: a part is missing", before, before, 112, 96)
    assert [c["ok"] for c in unread] == [False, True]
    assert index_jobs.index_faults({"index": {"generation": 2, "admitted": 15}}, 16) != []


def test_the_controls_fail_and_the_sweep_finds_equal_work_at_toy_size(capsys):
    assert control_index.main(["--workload", CELL, "--seeds", "12", "--rehearse"]) == 0
    printed = capsys.readouterr().out
    assert "control bfloat16" in printed and "correct = False, every value limit failed = True" in printed
    assert "control kept" in printed and printed.count("correct = False") == 2
    assert "the sound answers alone: correct = True" in printed
    assert margin_sweep_index.main(["--config", CONFIG, "--seeds", "3-4", "--rehearse"]) == 0
    assert "work differs from seed 3's on seeds: none" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_and_the_counters_gives_the_readers_nothing(name):
    reader = cells.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))
    parent = {"jobs": [{"wall_s": 9.0, "record": {"stages": {"index_rect_compare": {"seconds": 1.0}},
                                                  "phases": {"job": {"seconds": 9.0, "self_seconds": 9.0}}}}]}
    assert reader.read(parent) is None and reader.read({"jobs": []}) is None


def test_the_readers_on_a_record_of_the_cell(updated):
    made, job = updated[0]
    run = {"jobs": [job]}
    read = {n: cells.load_module(os.path.join(BENCH, "layer_metrics", n + ".py")).read(run) for n in NEW}
    assert all(v is not None and v > 0 for v in read.values()), read
    assert read["index_recomputed_member_share"] == pytest.approx(100.0 * 78 / 112)
    did = job["record"]["index"]
    assert read["index_published_bytes_per_genome"] == did["bytes_published"] / 16
    assert did["files_published"] == 3 and did["parts_written"] == 0 and did["tiles"] == 1


def test_a_rehearsal_prints_a_well_formed_line_with_every_metric_of_the_index_layer():
    seed = 2**31 + 50
    argv = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", str(seed),
            "--seconds", "4", "--trace", "1", "--rehearse"]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(argv, capture_output=True, text=True, env={**env, "JAX_PLATFORMS": "cpu"},
                          timeout=900, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line, out = json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert set(NEW) <= set(line["metrics"]) and "breakdown" in line
    assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
    assert "mash_kernel_ns_per_pair" not in line["metrics"]
    assert out.count("compare: ") == 19 and "WRONG" not in out and "job failed" not in out
    assert 'index: {"admitted": 16' in out
    assert not os.path.exists(os.path.join(BENCH, ".work", f"{CELL}-{seed}"))

"""The cell `gtdb_release_preempt_6k.compare_greedy_resume`: its files are found
by name wherever later cells put theirs, its deployment is `gtdb_release_6k`'s
word for word but for the three guarantees and the assumptions it is about, a
job's attempts become one record for the older readers, its four readers read
made-up attempts and give nothing where the program has no such span or
counter, a job that was not stopped where the mix stops it counts as failed,
the guarantees' comparisons pass a program that keeps its work and fail one
that keeps nothing, a program that cannot be stopped there is refused before
any set-up, and a rehearsal of the whole cell and of its control prints
well-formed lines."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import cells, control_resume, resume_jobs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "gtdb_release_preempt_6k.compare_greedy_resume"
CONFIG = "gtdb_release_preempt_6k"
TWIN_CONFIG, TWIN = "gtdb_release_6k", "gtdb_release_6k.compare_greedy"
NEW = {"resume_load_s": ("s", "program_span", "workflow"),
       "resume_repeat_s": ("s", "program_span", "workflow"),
       "resume_recomputed_share": ("%", "program_counter", "secondary compare"),
       "resume_drain_latency_ms": ("ms", "program_span", "workflow")}
LEFT_ALONE = {"idle_attributed", "setup_first_job_cold_s"}  # would lie on a job of three records
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _reader(name):
    return cells.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


# ---- made-up records: a job of 6 stripes and 378 clusters, stopped as the mix stops it ------


def _phases(**seconds):
    return {name: {"seconds": s, "self_seconds": s, "calls": 1, "thread": "main"}
            for name, s in seconds.items()}


def _rec(phases, stages=None, resume=None, drain=None, **over):
    rec = {"platform": "tpu", "device_kind": "TPU v5 lite", "n_devices": 1,
           "phases": _phases(**phases), "stages": stages or {}, "resume": resume or {}}
    if drain:
        rec["drain"] = drain
    rec.update(over)
    return rec


ENGINE = [{"rows": 576, "compared_pairs": 1900, "all_pairs": 165_600},
          {"rows": 224, "compared_pairs": 600, "all_pairs": 24_976}]


def _undisturbed():
    return _rec({"job": 8.8, "stage:ingest_or_cache": 0.55}, resume={"tiles_computed": 21,
                                                                      "clusters_computed": 378},
                stages={"primary_compare": {"pairs": 18_871_296, "seconds": 2.4, "calls": 1}},
                primary_stream_slots={"stripes": 6},
                secondary_calls=[{"rows_pad": 512, "width": 4096, "v_pad": 65536, "calls": 3,
                                  "clusters": 376, "rows": 1536, "useful_pairs": 7424}],
                secondary_greedy_calls=ENGINE, secondary_paths={"greedy_matmul": 2,
                                                                "one_shot_clusterlocal": 3})


def _job(in_flight=60, clusters_resumed=190, tiles_second=6, t0=1000.0):
    """A job as ``resume_jobs.run_job`` and ``read_records`` leave it: three
    attempts, the second with a batched call of `in_flight` more clusters
    than it published."""
    first = _rec({"job": 2.5, "stage:ingest_or_cache": 0.5, "primary/pack": 0.3, "primary/put": 0.2,
                  "primary/publish": 0.1},
                 stages={"primary_compare": {"pairs": 15_000_000, "seconds": 1.4, "calls": 1,
                                             "pairs_per_sec": 1.0e7}},
                 resume={"tiles_computed": 15},
                 drain={"stage": "primary", "stripes_published": 3, "next_stripe": 3,
                        "requested_monotonic_s": t0 + 2.46, "after_s": 2.4},
                 fault_tolerance={"injected_process_death_drain": 1})
    second = _rec({"job": 6.5, "stage:ingest_or_cache": 0.6, "primary/pack": 0.3, "primary/put": 0.2,
                   "primary/resume_load": 0.01, "primary/assemble": 0.05, "primary/linkage": 0.3,
                   "mdb_build": 0.1, "tables_io": 0.4, "evaluate/columns": 0.05,
                   "secondary/checkpoint": 0.5},
                  stages={"primary_compare": {"pairs": 3_871_296, "seconds": 0.6, "calls": 1},
                          "secondary_compare": {"pairs": 3000, "seconds": 3.0, "calls": 4}},
                  resume={"stripes_resumed": 3, "tiles_resumed": 15, "tiles_computed": tiles_second,
                          "shard_bytes": 90_000, "clusters_computed": 190 + in_flight},
                  drain={"stage": "secondary", "clusters_published": 190, "last_cluster": 4711,
                         "requested_monotonic_s": t0 + 9.07, "after_s": 6.4},
                  secondary_greedy_calls=ENGINE,
                  secondary_calls=[{"rows_pad": 512, "width": 4096, "v_pad": 65536, "calls": 2,
                                    "clusters": 188 + in_flight, "rows": 1000, "useful_pairs": 5000}],
                  secondary_paths={"greedy_matmul": 2, "one_shot_clusterlocal": 2},
                  fault_tolerance={"injected_secondary_checkpoint_drain": 1})
    third = _rec({"job": 3.4, "stage:ingest_or_cache": 0.55, "primary/pack": 0.3,
                  "primary/resume_load": 0.02, "secondary/resume_load": 0.2, "primary/linkage": 0.3,
                  "tables_io": 0.5, "secondary/checkpoint": 0.3, "stage:evaluate": 0.4},
                 stages={"primary_compare": {"pairs": 0, "seconds": 0.1, "calls": 1},
                         "secondary_compare": {"pairs": 1200, "seconds": 0.9, "calls": 2}},
                 resume={"stripes_resumed": 6, "tiles_resumed": 21, "tiles_computed": 0,
                         "shard_bytes": 180_000, "clusters_resumed": clusters_resumed,
                         "checkpoint_bytes": 900_000, "clusters_computed": 378 - clusters_resumed},
                 secondary_calls=[{"rows_pad": 512, "width": 4096, "v_pad": 65536, "calls": 1,
                                   "clusters": 100, "rows": 400, "useful_pairs": 2000},
                                  {"rows_pad": 256, "width": 4096, "v_pad": 32768, "calls": 1,
                                   "clusters": 88, "rows": 200, "useful_pairs": 724}],
                 secondary_paths={"one_shot_clusterlocal": 2},
                 process={"first_job": {"verb": "compare"}}, evaluate={"mdb": {"source": "job"}})
    held = [{"stripes": [], "clusters": []}, {"stripes": [0, 1, 2], "clusters": []},
            {"stripes": list(range(6)), "clusters": list(range(1, 191))}]
    attempts = [{"wall_s": w, "began_s": t0 + b, "returned_s": t0 + b + w, "exit": code, "error": None,
                 "fault": None, "published_before": h, "record": rec}
                for w, b, code, h, rec in ((2.5, 0.0, 0, held[0], first), (6.6, 2.52, 0, held[1], second),
                                           (3.5, 9.14, None, held[2], third))]
    return {"wall_s": 12.64, "workdir": "job0", "error": None, "attempts": attempts,
            "record": resume_jobs.merge_records([first, second, third]), "resolved": "streaming_sort"}


# ---- found by name -----------------------------------------------------------------------------


def test_the_cell_is_found_by_name_and_is_its_twin_s_deployment_word_for_word():
    loaded = cells.load_cell(CELL)
    spec, cfg, mix = loaded["spec"], loaded["config"], loaded["traffic"]
    assert loaded["cell"] == {**loaded["cell"], "config": CONFIG, "traffic": "compare_greedy_resume",
                              "chips": 1}
    assert len(loaded["cell"]["why"]) <= 200 and "6,144" in loaded["cell"]["why"]
    assert mix["kind"] == "resume_jobs" and hasattr(resume_jobs, "run")
    twin = cells.read_json(os.path.join(BENCH, "configs", TWIN_CONFIG + ".json"))
    # nothing that shapes the data differs: generator, table, shapes, thresholds, the layout's seed
    for key in ("generator", "params", "data", "rehearse", "reduced", "reduced_why"):
        assert cfg[key] == twin[key], key
    assert cfg["reduced"] == ["n"] and cfg["data"]["n"] == 6144 and cfg["data"]["layout_seed"] == 34
    assert cfg["guarantees"][:4] == twin["guarantees"] and len(cfg["guarantees"]) == 7
    assert "byte for byte" in cfg["guarantees"][4] and "not computed again" in cfg["guarantees"][5]
    assert "at most the one device call in flight" in cfg["guarantees"][5]
    assert "exit code 0" in cfg["guarantees"][6] and "record" in cfg["guarantees"][6]
    assert {k: v for k, v in cfg["assumed"].items() if k in twin["assumed"]} == twin["assumed"]
    assert set(cfg["assumed"]) - set(twin["assumed"]) == {
        "stop with notice", "graceful, not a SIGKILL", "where it is stopped"}
    assert "model knowledge" in cfg["assumed"]["stop with notice"]
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "config 5" in entry["source"] and "--greedy_secondary_clustering" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["source"] not in [c["source"] for c in spec["configs"] if c["name"] != CONFIG]
    assert [w["name"] for w in spec["workloads"] if w["config"] == CONFIG] == [CELL]
    # the twin's argv, routes, comparison and value limits
    greedy = cells.read_json(os.path.join(BENCH, "traffic", "compare_greedy.json"))
    assert mix["argv"] == greedy["argv"] and mix["compare"] == greedy["compare"]
    assert mix["expect"] == greedy["expect"]
    assert {k: v for k, v in mix["limits"].items() if k != "clusters_twice"} == greedy["limits"]
    assert set(mix["limits_why"]) == set(mix["limits"])
    from drep_tpu.cluster.controller import BATCH_ROWS_MAX

    assert mix["limits"]["clusters_twice"] == BATCH_ROWS_MAX // 2  # one batched call's clusters
    # nine cells, three on four chips, eight configurations
    assert len(spec["workloads"]) >= 9 and len(spec["configs"]) >= 8
    assert [w["name"] for w in spec["workloads"]].index(CELL) == 8
    assert sum(w["chips"] == 4 for w in spec["workloads"][:9]) == 3
    assert [m["name"] for m in cells.metrics_of(spec, CELL, "end_to_end")] == ["setup_s", "job_wall_s"]


def test_the_cell_is_appended_where_a_job_of_three_records_reads_true_and_brings_four_metrics():
    spec = cells.load_cell(CELL)["spec"]
    mine = {m["name"] for m in cells.metrics_of(spec, CELL, "per_layer")}
    twin = {m["name"] for m in cells.metrics_of(spec, TWIN, "per_layer")}
    assert twin - mine == LEFT_ALONE and mine - twin == set(NEW)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name, (unit, source, layer) in NEW.items():
        m = by_name[name]
        assert (m["unit"], m["source"], m["layer"], m["better"], m["moves"], m["workloads"]) == \
            (unit, source, layer, "lower", "job_wall_s", [CELL])
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    # appended, never inserted: the cells that were there before it keep their places
    before = [w["name"] for w in spec["workloads"]][:8]
    for m in spec["per_layer"] + spec["end_to_end"]:
        listed = m.get("workloads", [])
        if CELL in listed:
            assert [w for w in listed if w in before] == listed[:listed.index(CELL)]


def test_the_mix_stops_a_job_once_in_each_stage_by_the_program_s_own_fault_mode():
    from drep_tpu.utils import faults

    mix = cells.load_cell(CELL)["traffic"]
    for stops in (mix["stops"], mix["rehearse"]["stops"]):
        assert [s["stage"] for s in stops] == ["primary", "secondary"]
        rules = faults._parse(",".join(s["fault"] for s in stops))
        first, second = rules["process_death"][0], rules["secondary_checkpoint"][0]
        assert first.mode == second.mode == "drain"
        # `process_death` fires at a stripe's head, `secondary_checkpoint` after a checkpoint
        assert first.skip == stops[0]["after"] and second.skip == stops[1]["after"] - 1
        assert all(0 < s["after"] < s["of"] for s in stops)
    assert [(s["after"], s["of"]) for s in mix["stops"]] == [(3, 6), (190, 378)]


# ---- one record a job ---------------------------------------------------------------------------


def test_a_job_s_attempts_become_one_record_whose_seconds_and_counters_add_up():
    job = _job()
    rec = job["record"]
    assert rec["phases"]["job"]["seconds"] == pytest.approx(12.4) and rec["phases"]["job"]["calls"] == 3
    assert rec["phases"]["stage:ingest_or_cache"]["self_seconds"] == pytest.approx(1.65)
    assert rec["phases"]["stage:evaluate"]["seconds"] == 0.4 and rec["phases"]["job"]["thread"] == "main"
    assert rec["stages"]["primary_compare"] == {"pairs": 18_871_296, "seconds": pytest.approx(2.1),
                                                "calls": 3}  # a rate is no sum
    assert rec["stages"]["secondary_compare"]["pairs"] == 4200
    assert rec["resume"]["tiles_computed"] == 21 and rec["resume"]["clusters_computed"] == 438
    assert rec["secondary_paths"] == {"greedy_matmul": 2, "one_shot_clusterlocal": 4}
    assert rec["fault_tolerance"] == {"injected_process_death_drain": 1,
                                      "injected_secondary_checkpoint_drain": 1}
    shapes = {(c["rows_pad"], c["v_pad"]): c for c in rec["secondary_calls"]}
    assert shapes[(512, 65536)]["calls"] == 3 and shapes[(512, 65536)]["useful_pairs"] == 7000
    assert shapes[(256, 32768)]["calls"] == 1 and len(rec["secondary_greedy_calls"]) == 2
    assert "drain" not in rec and rec["process"] == {"first_job": {"verb": "compare"}}
    assert rec["evaluate"] == {"mdb": {"source": "job"}} and rec["platform"] == "tpu"
    # the accepted readers read it as they read any job's record
    run = {"jobs": [job], "device": DEVICE}
    assert _reader("load_sketches_s").read(run) == pytest.approx(1.65)
    assert _reader("secondary_checkpoint_s").read(run) == pytest.approx(0.8)
    assert _reader("primary_stage_s").read(run) == pytest.approx(2.1)
    assert _reader("host_rest_s").read(run) == pytest.approx(12.64 - 2.1 - 3.9)
    assert _reader("evaluate_s").read(run) == 0.4


# ---- the four readers ---------------------------------------------------------------------------


def test_the_readers_on_a_job_of_three_attempts():
    run = {"jobs": [_job(), _job(in_flight=0)], "device": DEVICE, "undisturbed": _undisturbed()}
    assert _reader("resume_load_s").read(run) == pytest.approx(0.01 + 0.02 + 0.2)
    # attempts 1 and 2, not the last: 0.5+0.3+0.2, then 0.6+0.3+0.2+0.05+0.3+0.1+0.4+0.05
    assert _reader("resume_repeat_s").read(run) == pytest.approx(1.0 + 2.0)
    # the primary compared its pairs once; the secondary the engine's 2,500 once and 7,724 pairs
    # inside batched calls (5,000, then 2,000 and 724) where an undisturbed job makes 7,424
    once, done = 2500 + 7424, 2500 + 7724
    assert _reader("resume_recomputed_share").read(run) == pytest.approx(100 * ((done - once) / once) / 2)
    # the larger of the two: the record's stamp to the attempt's return, 2.50 - 2.46 and 9.12 - 9.07
    assert _reader("resume_drain_latency_ms").read(run) == pytest.approx(50.0, abs=0.01)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_program_or_a_kind_without_them_gives_the_readers_nothing(name):
    read = _reader(name).read
    parent = {"platform": "tpu", "phases": _phases(job=8.8), "stages": {}}  # no resume, no drain
    assert read({}) is None and read({"jobs": []}) is None
    assert read({"jobs": [{"wall_s": 8.8, "record": parent}], "device": DEVICE}) is None
    # an undisturbed job of this program: it opens no resume_load span and was never stopped
    plain = {"wall_s": 8.8, "record": _undisturbed()}
    assert read({"jobs": [plain], "device": DEVICE, "undisturbed": _undisturbed()}) is None
    if name == "resume_recomputed_share":  # no undisturbed job's record, or the parent's
        assert read({"jobs": [_job()], "device": DEVICE}) is None
        assert read({"jobs": [_job()], "device": DEVICE, "undisturbed": parent}) is None


# ---- a job that was not stopped where the mix stops it ------------------------------------------


def _with(job, k, **over):
    job["attempts"][k] = {**job["attempts"][k], **over}
    return job


def _drained_at(job, k, **drain):
    job["attempts"][k]["record"]["drain"].update(drain)
    return job


@pytest.mark.parametrize("job,fault", [
    (_job(), None),
    (_with(_job(), 0, exit=None), "attempt 1 was to drain in the primary and ended with exit None"),
    (_drained_at(_job(), 0, stage="secondary"), "attempt 1 was to drain in the primary"),
    (_drained_at(_job(), 0, stripes_published=4), "stripes_published=4, the mix means 3"),
    (_drained_at(_job(), 1, clusters_published=189), "clusters_published=189, the mix means 190"),
    (_with(_job(), 1, record=None), "attempt 2 left no record"),
    (_with(_job(), 2, exit=0), "the last attempt was to run to its end"),
    ({**_job(), "attempts": _job()["attempts"][:2]}, "2 attempt(s), the mix means 3"),
])
def test_a_job_that_was_not_stopped_where_the_mix_stops_it_counts_as_failed(job, fault):
    stops = cells.load_cell(CELL)["traffic"]["stops"]
    faults = resume_jobs.stop_faults(job, stops)
    assert (faults == []) if fault is None else any(fault in f for f in faults), faults


def test_every_attempt_s_record_is_judged_and_the_routes_over_the_whole_job(monkeypatch):
    mix = cells.load_cell(CELL)["traffic"]

    def judged(job, rehearse=False):
        for attempt in job["attempts"]:  # as run_job leaves them: the record's bytes
            attempt["record_bytes"] = json.dumps(attempt.pop("record")).encode()
        not_held: list = []
        monkeypatch.setattr("drep_tpu.workdir.WorkDirectory.get_arguments",
                            lambda self, name: {"primary_estimator_resolved": job["resolved"]})
        resume_jobs.judge(job, mix["stops"], DEVICE, mix["expect"], rehearse, not_held)
        return job["error"], not_held

    batched = {"secondary_greedy_batched": {"clusters": 376, "compared_pairs": 1300}}
    sound = _job()
    sound["attempts"][2]["record"].update(batched)
    assert judged(sound) == (None, [])
    hidden = _job()
    hidden["attempts"][2]["record"].update(batched)
    hidden["attempts"][1]["record"]["fault_tolerance"]["retries"] = 2
    assert "attempt 2: work did not run where it was meant to: {'retries': 2}" in judged(hidden)[0]
    cpu = _job()
    cpu["attempts"][2]["record"].update(batched)
    cpu["attempts"][0]["record"]["platform"] = "cpu"
    assert "attempt 1: record says platform='cpu'" in judged(cpu)[0]
    assert "holds no secondary_greedy_batched" in judged(_job())[0]
    gather = _job()
    gather["attempts"][2]["record"].update(batched)
    gather["attempts"][1]["record"]["secondary_paths"] = {"greedy_gather": 2, "one_shot_clusterlocal": 2}
    assert "secondary path 'greedy_matmul' did not serve" in judged(gather)[0]
    soft = _job()
    soft["attempts"][2]["record"].update(batched)
    soft["attempts"][1]["record"]["secondary_paths"] = {"greedy_gather": 2, "one_shot_clusterlocal": 2}
    error, not_held = judged(soft, rehearse=True)  # a rehearsal says it and fails nothing for it
    assert error is None and len(not_held) == 2
    dense = {**_job(), "resolved": "sort"}
    dense["attempts"][2]["record"].update(batched)
    assert "primary estimator resolved to 'sort'" in judged(dense)[0]


# ---- the guarantees, counted ----------------------------------------------------------------------


def _compared(jobs, monkeypatch, moved=0):
    digests = iter([{"Cdb": "x"}] * (len(jobs) - moved) + [{"Cdb": "y"}] * moved)
    monkeypatch.setattr(resume_jobs, "table_digests", lambda wd: next(digests))
    limits = cells.load_cell(CELL)["traffic"]["limits"]
    return resume_jobs.guarantee_comparisons(
        jobs, {"digests": {"Cdb": "x"}, "record": _undisturbed()}, limits)


def test_a_program_that_keeps_its_work_passes_the_four_comparisons(monkeypatch):
    out = _compared([_job(), _job(in_flight=17)], monkeypatch)
    assert [c["value"] for c in out] == [0, 0, 0, 60] and all(c["ok"] for c in out)
    assert [c["limit"] for c in out] == [0, 0, 0, 256]
    assert "byte for byte" in out[0]["what"] and "computes 378" in out[3]["what"]


def test_a_program_that_keeps_nothing_fails_the_two_limit_0_counts(monkeypatch):
    # attempt 2 dispatched all 21 tiles with three stripes published; attempt 3 looked nothing up
    emptied = _job(tiles_second=21, clusters_resumed=0)
    emptied["attempts"][2]["record"]["resume"]["tiles_computed"] = 21
    out = _compared([emptied], monkeypatch)
    assert [c["ok"] for c in out] == [True, False, False, True]
    # every cluster of attempt 2 is computed again, 250 of them: still under one call's 256, so
    # the control is held to the two limit-0 counts
    assert [c["value"] for c in out] == [0, 15 + 21, 190, 250 + 378 - 378]
    # a whole batched call and more computed twice is over the guarantee's limit
    assert not _compared([_job(in_flight=257)], monkeypatch)[3]["ok"]
    assert _compared([_job(in_flight=256)], monkeypatch)[3]["ok"]
    assert not _compared([_job(), _job()], monkeypatch, moved=1)[0]["ok"]


def test_what_the_stores_hold_is_listed_by_stripe_and_cluster_and_emptied_by_the_control(tmp_path):
    wd = str(tmp_path)
    assert resume_jobs.published(wd) == {"stripes": [], "clusters": []}
    rows, pcs = tmp_path / "data" / "streaming_primary", tmp_path / "data" / "secondary_checkpoints"
    rows.mkdir(parents=True), pcs.mkdir(parents=True)
    for name in ("row_00000.npz", "row_00002.e01.npz", "meta.json", ".pod-hb.p0", "row_00001.npz.tmp"):
        (rows / name).write_bytes(b"x")
    for name in ("pc_000007.npz", "pc_000190.npz", "meta.json"):
        (pcs / name).write_bytes(b"x")
    assert resume_jobs.published(wd) == {"stripes": [0, 2], "clusters": [7, 190]}
    resume_jobs.empty_stores(wd)
    assert resume_jobs.published(wd) == {"stripes": [], "clusters": []}


# ---- a program that cannot be stopped there --------------------------------------------------------


def test_a_program_that_cannot_be_stopped_where_the_cell_stops_it_is_refused_before_set_up(monkeypatch):
    from benchmark import greedy_jobs
    from drep_tpu.utils import faults

    mix = cells.load_cell(CELL)["traffic"]
    resume_jobs.refuse_a_program_that_cannot_stop(mix["stops"], mix["expect"])  # this program: fine
    assert not faults.active()  # and nothing is left configured
    # the parent's registry: no such site
    monkeypatch.setattr(faults, "SITES", tuple(s for s in faults.SITES if s != "secondary_checkpoint"))
    with pytest.raises(SystemExit, match="cannot be stopped where the cell stops it: unknown fault site"):
        resume_jobs.refuse_a_program_that_cannot_stop(mix["stops"], mix["expect"])
    monkeypatch.undo()
    # a registry that knows the site and a record that cannot say what was resumed
    monkeypatch.setattr(greedy_jobs, "counters_unknown", lambda expect: ["resume", "drain"])
    with pytest.raises(SystemExit, match="record has no \\['resume', 'drain'\\]"):
        resume_jobs.refuse_a_program_that_cannot_stop(mix["stops"], mix["expect"])


# ---- the whole cell and its control, rehearsed ------------------------------------------------------


def _one_device_env():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    return {**env, "JAX_PLATFORMS": "cpu"}


def test_a_rehearsal_stops_a_job_twice_and_prints_a_well_formed_line():
    seed = 2**31 + 47
    argv = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", str(seed),
            "--seconds", "1", "--trace", "1", "--rehearse"]
    proc = subprocess.run(argv, capture_output=True, text=True, env=_one_device_env(), timeout=900,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line, out = json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 1
    assert line["rehearsal"] is True and line["device"] == {**line["device"], "platform": "cpu", "count": 1}
    listed = {m["name"] for m in cells.metrics_of(cells.load_cell(CELL)["spec"], CELL, "per_layer")}
    # every metric the cell lists reads a number, but the two that need a TPU's kernel and peaks
    # and the span of the matmul route's puts (off a TPU the engine takes its gather route)
    assert listed - set(line["metrics"]) == {"mash_kernel_ns_per_pair", "secondary_greedy_roofline",
                                             "secondary_greedy_put_s"}
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["compiles_in_window.batch"] == 0
    assert metrics["resume_load_s"] > 0 and metrics["resume_repeat_s"] > 0
    assert 0 < metrics["resume_drain_latency_ms"] < 5000
    # 400 genomes: the primary computes every pair once; the one batched call of 18 clusters was in
    # flight when the tenth checkpoint was published, so ten clusters' pairs are computed twice
    assert 0 < metrics["resume_recomputed_share"] < 10
    # the greedy rule's nine comparisons and the guarantees' four, each beside its limit
    assert out.count("compare: ") == 13 and "WRONG" not in out and "job failed" not in out
    assert "clusters computed twice over a job (an undisturbed job computes 20), worst job = 10 " \
        "(limit 256) ok" in out
    attempts = json.loads(next(ln for ln in out.splitlines() if ln.startswith("attempts: "))[10:])
    assert [a["drain"] and a["drain"]["stage"] for a in attempts] == ["primary", "secondary", None]
    assert [a["held_before"] for a in attempts] == [
        {"stripes": 0, "clusters": 0}, {"stripes": 1, "clusters": 0}, {"stripes": 2, "clusters": 10}]
    assert attempts[2]["resume"]["clusters_resumed"] == 10 == attempts[2]["resume"]["clusters_computed"]
    assert not os.path.exists(os.path.join(BENCH, ".work", f"{CELL}-{seed}"))


def test_the_control_fails_the_two_limit_0_counts_and_moves_no_table():
    argv = [sys.executable, os.path.join(BENCH, "control_resume.py"), "--workload", CELL,
            "--seeds", str(2**31 + 48), "--rehearse"]
    proc = subprocess.run(argv, capture_output=True, text=True, env=_one_device_env(), timeout=900,
                          cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    wrong = [ln for ln in proc.stdout.splitlines() if ln.endswith("WRONG")]
    assert len(wrong) == 2 and "tiles dispatched inside stripes" in wrong[0]
    assert "clusters computed among those published" in wrong[1]
    assert "not byte for byte the undisturbed job's = 0 (limit 0) ok" in proc.stdout
    assert "both limit-0 counts failed and no table moved = True" in proc.stdout
    assert hasattr(control_resume, "control")

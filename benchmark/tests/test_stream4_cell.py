"""The cell `gtdb_release_25k.primary_stream4`: its files are found by name
wherever later cells put theirs, its size tables obey ISSUE 39's limits, its
generator gives every seed the same layout and spreads every cluster over the
stripes, its reference gives the planted clusters, its check passes the
planted answer and refuses a merged cluster, a missing or an added Mdb pair
and one hash count off, its control fails the Mash limit, its three readers
read a made-up record and trace and give nothing where the program has no
such counter or span, a job whose tiles did not reach the cell's chips counts
as failed, and a rehearsal of the whole cell on four virtual devices prints a
well-formed line."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import batch_jobs, cells, check, greedy_jobs, stream4_jobs
from benchmark import reference as ref
from benchmark.tests.test_greedy_cell import (  # the same alterations, the same job's form
    _as_a_job_writes_it, _merge_two_clusters, _one_bottom_hash_off)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "gtdb_release_25k.primary_stream4"
CONFIG = "gtdb_release_25k"
NEW = ["stream_turn_pad_share", "stream_slot_balance", "stream_chip_occupancy"]
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def _reader(name):
    return cells.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


@pytest.fixture(scope="module")
def toy():
    loaded = cells.load_cell(CELL)
    cfg = loaded["config"]
    cfg = {**cfg, "data": {**cfg["data"], **cfg["rehearse"]}}
    data = loaded["generator"].generate(cfg["data"], 2**31 + 39)
    return {"cfg": cfg, "mix": loaded["traffic"], "gen": loaded["generator"], "data": data,
            "want": stream4_jobs.expected_answers(data, cfg["params"])}


def _slots(tiles=(75, 75, 75, 75), stripes=24, turns=84):
    return {"slots": len(tiles), "stripes": stripes, "tiles": sum(tiles), "turns": turns,
            "by_slot": [{"tiles": t, "pairs": t * 1024 * 1024, "put_bytes": 201_523_200,
                         "finalize_wait_s": 1.9} for t in tiles]}


def _record(**over):
    rec = {"platform": "tpu", "device_kind": "TPU v5 lite", "n_devices": 4,
           "gauges": {"streaming_devices_used": 4.0}, "primary_stream_slots": _slots(),
           "stages": {"primary_compare": {"pairs": 301_977_600, "tiles_computed": 300}}}
    rec.update(over)
    return rec


# ---- found by name -----------------------------------------------------------------------


def test_the_cell_is_found_by_name_wherever_later_cells_are_appended():
    loaded = cells.load_cell(CELL)
    assert loaded["cell"] == {**loaded["cell"], "config": CONFIG, "traffic": "primary_stream4",
                              "chips": 4}
    assert loaded["traffic"]["kind"] == "stream4_jobs" and hasattr(stream4_jobs, "run")
    cfg, mix, spec = loaded["config"], loaded["traffic"], loaded["spec"]
    assert cfg["generator"] == "planted_release" and cfg["data"]["n"] == 24576 == 24 * 1024
    assert cfg["reduced"] == ["n", "s_scaled"] == list(cfg["reduced_why"])
    assert {"clusters", "layout_seed", "accessory_max"} <= set(cfg["assumed"])
    # the guarantees and the thresholds are those of the ring's deployment, word for word
    ring = cells.read_json(os.path.join(BENCH, "configs", "gtdb_reps_10k.json"))
    assert cfg["guarantees"] == ring["guarantees"] and cfg["params"] == ring["params"]
    assert (cfg["data"]["s_bottom"], cfg["data"]["kmer_size"]) == (1000, 21)
    stream = cells.read_json(os.path.join(BENCH, "traffic", "primary_stream.json"))
    assert mix["argv"] == stream["argv"] and mix["compare"] == ["primary", "mdb"]
    assert mix["limits"] == stream["limits"] and set(mix["limits_why"]) == set(mix["limits"])
    assert mix["expect"] == {"primary_estimator_resolved": "streaming_sort"}
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "config 5" in entry["source"] and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert [w["name"] for w in spec["workloads"] if w["config"] == CONFIG] == [CELL]
    assert [m["name"] for m in cells.metrics_of(spec, CELL, "end_to_end")] == ["setup_s", "job_wall_s"]
    mine = {m["name"] for m in cells.metrics_of(spec, CELL, "per_layer")}
    assert set(NEW) <= mine
    assert {"host_rest_s", "primary_stage_s", "primary_prep_s", "primary_device_wait_s", "primary_post_s",
            "primary_linkage_s", "load_sketches_s", "tables_s", "mash_kernel_ns_per_pair",
            "device_idle.batch", "idle_attributed", "host_unattributed_s", "compiles_in_window.batch",
            "setup_pre_job_s", "setup_programs"} <= mine
    assert not {m for m in mine if m.startswith(("secondary_", "ingest_", "ring_"))}
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:  # wherever they stand in the list
        assert by_name[name]["moves"] == "job_wall_s" and by_name[name]["workloads"] == [CELL]
        assert by_name[name]["unit"] == "%" and by_name[name]["layer"] == "primary compare"
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    assert by_name["stream_chip_occupancy"]["source"] == "device_trace"
    # at most half of the cells, rounded down, ask for four chips
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= len(spec["workloads"]) // 2
    # appended where it was added: the cells that were there before it keep their places
    before = [w["name"] for w in spec["workloads"]]
    before = before[:before.index(CELL)]
    for m in spec["per_layer"] + spec["end_to_end"]:
        listed = m.get("workloads", [])
        if CELL in listed:
            assert [w for w in listed if w in before] == listed[:listed.index(CELL)]


@pytest.mark.parametrize("size", ["data", "rehearse"])
def test_the_size_tables_obey_the_limits_the_issue_sets(size):
    cfg = cells.read_json(os.path.join(BENCH, "configs", CONFIG + ".json"))
    data = {**cfg["data"], **(cfg[size] if size == "rehearse" else {})}
    table = data["clusters"]
    sizes = [c["size"] for c in table for _ in range(c["count"])]
    assert sum(sizes) == data["n"] and all(c["groups"] == [c["size"]] for c in table)
    singles = sum(1 for s in sizes if s == 1)
    assert abs(singles / data["n"] - 0.64) <= 0.05  # the singletons' share, within five points
    # no two genomes of a cluster may tie in size: the accessory hashes have to allow it
    assert max(sizes) <= int(data["accessory_max"] * data["s_scaled"]) + 1
    if size == "data":
        assert data["n"] == 24576 and 512 <= max(sizes) <= 1024
        assert singles / len(sizes) > 0.9  # and of the clusters
        assert sum(s * (s - 1) // 2 for s in sizes) == 586_240  # the pairs inside clusters
        assert data["s_scaled"] == data["s_bottom"] == 1000  # the least the generator can plant
    else:
        assert data["n"] <= 1024


# ---- the generator ---------------------------------------------------------------------------


def test_every_seed_gets_the_same_slot_table_and_hash_counts(toy):
    gen, params = toy["gen"], toy["cfg"]["data"]
    table = gen.plan(params).slot_table()
    first = toy["data"]
    for seed in (1, 39, 2**31 + 40, 3_000_003_901):
        data = gen.generate(params, seed)
        assert gen.plan(params).slot_table() == table
        assert [len(s) for s in data.scaled] == [len(s) for s in first.scaled]
        assert all(len(b) == 1000 for b in data.bottom)
        assert np.array_equal(data.primary_labels, first.primary_labels)
        assert not np.array_equal(data.bottom[0], first.bottom[0])  # the values are the seed's


def test_the_order_spreads_every_cluster_over_the_stripes():
    cfg = cells.load_cell(CELL)
    laid = cfg["generator"].plan(cfg["config"]["data"])
    stripe = np.arange(24576) // 1024
    sizes = np.bincount(laid.cluster)
    for c in np.flatnonzero(sizes >= 96):
        held = np.bincount(stripe[laid.cluster == c], minlength=24)
        # some of it in every stripe (a cluster of 96 has four genomes a stripe: nearly every)
        assert np.count_nonzero(held) >= (24 if sizes[c] >= 192 else 20), (c, held)
        assert held.max() <= 3 * sizes[c] / 24 + 4  # and no stripe holds the cluster
    # the planted order is not the table's: the first stripe holds clusters of every size
    assert len({int(sizes[c]) for c in laid.cluster[:1024]}) >= 9


# ---- the reference and the check -------------------------------------------------------------


def test_the_reference_gives_the_planted_clusters_and_reference_py_s_values(toy):
    data, want = toy["data"], toy["want"]
    assert ref.partition_of(want["primary"]) == ref.partition_of(data.primary_labels)
    mash = want["mash"]
    assert np.all(mash["i"] < mash["j"]) and np.all(mash["dist"] < 0.1)
    sizes = np.bincount(data.primary_labels)
    assert len(mash["i"]) == sum(int(s) * (int(s) - 1) // 2 for s in sizes)  # clusters share nothing
    for at in (0, len(mash["i"]) // 2, -1):
        i, j = int(mash["i"][at]), int(mash["j"][at])
        assert mash["dist"][at] == ref.mash_distance(
            ref.mash_jaccard(data.bottom[i], data.bottom[j], 1000), 21)


def _check(toy, got):
    return greedy_jobs.check_greedy(got, toy["data"], toy["cfg"]["params"], toy["mix"]["compare"],
                                    toy["mix"]["limits"], expected=toy["want"])


def test_the_check_passes_the_planted_answer(toy, capsys):
    out = _check(toy, _as_a_job_writes_it(toy["want"]))
    assert len(out) == 4 and check.report(out)
    assert capsys.readouterr().out.count("compare: ") == 4


def _drop_a_pair(got, toy):
    m = got["mash"]
    keep = ~(((m["i"] == m["i"][0]) & (m["j"] == m["j"][0])) | ((m["i"] == m["j"][0]) & (m["j"] == m["i"][0])))
    got["mash"] = {k: v[keep] for k, v in m.items()}


def _add_a_pair(got, toy):
    # two genomes of different clusters under distance 1: a pair the reference does not know
    a, b = 0, int(np.flatnonzero(got["primary"] != got["primary"][0])[0])
    m = got["mash"]
    got["mash"] = {"i": np.append(m["i"], a), "j": np.append(m["j"], b), "dist": np.append(m["dist"], 0.2)}


@pytest.mark.parametrize("alter,wrong", [
    (_merge_two_clusters, "genomes in a primary cluster the reference does not have"),
    (_drop_a_pair, "Mdb pairs missing, or present and not in the reference"),
    (_add_a_pair, "Mdb pairs missing, or present and not in the reference"),
    (_one_bottom_hash_off, "largest Mash distance error"),
])
def test_the_check_refuses_an_altered_answer_by_the_comparison_it_touches(toy, alter, wrong):
    got = _as_a_job_writes_it(toy["want"])
    alter(got, toy)
    out = _check(toy, got)
    failed = [c for c in out if not c["ok"]]
    assert len(failed) == 1 and failed[0]["what"].startswith(wrong), failed
    if alter is _one_bottom_hash_off:  # one count in 1,000: 3e-5 and more, over the 1e-5
        assert 1e-5 < failed[0]["value"] < 1e-3


def test_the_control_in_bfloat16_fails_the_mash_limit_and_nothing_else(toy):
    low = stream4_jobs.expected_answers(toy["data"], toy["cfg"]["params"], lower_precision=True)
    out = _check(toy, low)
    assert [c["ok"] for c in out] == [True, True, True, False]
    assert out[3]["what"].startswith("largest Mash distance error")
    assert out[3]["value"] > 3 * toy["mix"]["limits"]["mash_dist"]


# ---- the readers -----------------------------------------------------------------------------


def test_the_readers_on_a_record_and_a_trace_of_the_cell():
    rec = _record()
    run = {"jobs": [{"wall_s": 14.0, "record": rec}, {"wall_s": 14.2, "record": rec}], "device": DEVICE}
    # 300 tiles in 84 turns of four chips: 36 of 336 places hold no tile
    assert _reader("stream_turn_pad_share").read(run) == pytest.approx(100 * (1 - 300 / 336))
    assert _reader("stream_slot_balance").read(run) == 100.0
    uneven = _record(primary_stream_slots=_slots(tiles=(80, 76, 74, 70)))
    assert _reader("stream_slot_balance").read({"jobs": [{"record": uneven}]}) == pytest.approx(87.5)
    unreached = _record(primary_stream_slots=_slots(tiles=(150, 150, 0, 0)))
    assert _reader("stream_slot_balance").read({"jobs": [{"record": unreached}]}) == 0.0
    one = _record(primary_stream_slots=_slots(tiles=(15,), stripes=5, turns=15))
    assert _reader("stream_turn_pad_share").read({"jobs": [{"record": one}]}) == 0.0
    assert _reader("stream_slot_balance").read({"jobs": [{"record": one}]}) == 100.0
    # occupancy: two stripes of 1 s; chip 0 busy 0.5 + 0.25 s inside them (and 1 s outside, which
    # does not count), chip 1 busy 0.5 s across the first stripe's end (0.25 inside), two chips idle
    host = [("drep:job", 0.0, 10e9), ("drep:stripe", 1e9, 1e9), ("drep:stripe", 3e9, 1e9),
            ("drep:primary/wait", 1.2e9, 0.5e9)]
    devices = {"/device:TPU:0": [("mash", 1.0e9, 0.5e9), ("fusion", 3.5e9, 0.25e9), ("copy", 5e9, 1e9)],
               "/device:TPU:1": [("mash", 1.75e9, 0.5e9)], "/device:TPU:2": []}
    traced = {**run, "trace": {"events": {"host": host, "devices": devices}}}
    assert _reader("stream_chip_occupancy").read(traced) == pytest.approx(100 * 1.0 / (4 * 2.0))
    full = {"/device:TPU:%d" % d: [("mash", 1e9, 1e9), ("mash", 3e9, 1e9)] for d in range(4)}
    traced["trace"]["events"]["devices"] = full
    assert _reader("stream_chip_occupancy").read(traced) == pytest.approx(100.0)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counter_or_the_span_gives_the_readers_nothing(name):
    parent = {k: v for k, v in _record().items() if k != "primary_stream_slots"}
    events = {"host": [("drep:job", 0.0, 1e9), ("drep:primary/wait", 0.0, 1e9)],
              "devices": {"/device:TPU:0": [("mash", 1.0, 5.0)]}}
    run = {"jobs": [{"wall_s": 14.0, "record": parent}], "device": DEVICE, "trace": {"events": events}}
    assert _reader(name).read(run) is None
    assert _reader(name).read({"jobs": [{"wall_s": 1.0, "record": {"stages": {}}}]}) is None
    assert _reader(name).read({"jobs": []}) is None and _reader(name).read({}) is None
    if name == "stream_chip_occupancy":  # an untraced run
        assert _reader(name).read({"jobs": [{"record": _record()}], "device": DEVICE, "trace": None}) is None


# ---- a job's own record ------------------------------------------------------------------------


@pytest.mark.parametrize("record,fault", [
    (_record(), None),
    (_record(gauges={"streaming_devices_used": 8.0}), None),
    (_record(gauges={"streaming_devices_used": 1.0}), "reached 1 device(s), the cell asks for 4"),
    (_record(gauges={"streaming_devices_used": 3.0}), "reached 3 device(s)"),
    (_record(gauges={}), "holds no gauge streaming_devices_used"),
    (_record(fault_tolerance={"retries": 1}), "did not run where it was meant to"),
    (_record(fault_tolerance={"watchdog_trips": 1, "cpu_fallback_tiles": 1}), "did not run where it was meant to"),
    (_record(fault_tolerance={"quarantined_devices": 1}), "did not run where it was meant to"),
    (_record(n_devices=1), "record says n_devices=1"),
    (_record(platform="cpu"), "record says platform"),
])
def test_a_job_that_did_not_run_as_the_cell_means_counts_as_failed(record, fault):
    expect = cells.load_cell(CELL)["traffic"]["expect"]
    faults = (batch_jobs.record_faults(record, DEVICE, expect, "streaming_sort")
              + stream4_jobs.slot_faults(record, 4))
    assert (faults == []) if fault is None else any(fault in f for f in faults), faults
    assert any("resolved" in f for f in batch_jobs.record_faults(record, DEVICE, expect, "ring_sort"))


def test_the_runner_counts_a_job_off_the_chips_as_failed_and_keeps_the_sound_ones(toy, monkeypatch, tmp_path):
    """``run`` on a window handed to it: ``batch_jobs.run`` is replaced, the
    record's reach and the comparison are the kind's own."""
    data, want = toy["data"], _as_a_job_writes_it(toy["want"])

    def window(ctx):
        ctx["generator"].prepare(ctx["config"], ctx["seed"], str(tmp_path))
        jobs = [{"wall_s": 14.0 + i, "workdir": f"job{i}", "error": None, "record": rec}
                for i, rec in enumerate([_record(), _record(gauges={"streaming_devices_used": 1.0}),
                                         _record()])]
        return {"correct": True, "attempted": 4, "failed": 1, "end_to_end": {"setup_s": 30.0},
                "run": {"jobs": jobs, "trace": None, "window_s": 45.0}}

    class Gen:
        @staticmethod
        def prepare(config, seed, out_dir):
            return {"workdir": out_dir, "data": data}

    monkeypatch.setattr(batch_jobs, "run", window)
    monkeypatch.setattr(stream4_jobs, "read_answers", lambda wd, names: want)
    ctx = {"config": toy["cfg"], "traffic": toy["mix"], "cell": {"chips": 4}, "generator": Gen, "seed": 7}
    out = stream4_jobs.run(ctx)
    assert out["correct"] is True and (out["attempted"], out["failed"]) == (4, 2)
    assert [j["workdir"] for j in out["run"]["jobs"]] == ["job0", "job2"]
    assert out["end_to_end"] == {"setup_s": 30.0, "job_wall_s": 15.0}
    monkeypatch.setattr(stream4_jobs, "read_answers", lambda wd, names: (_merge_two_clusters(want, toy), want)[1])
    assert stream4_jobs.run(ctx)["correct"] is False
    ctx["cell"] = {"chips": 8}
    with pytest.raises(SystemExit, match="no job of the window ran soundly"):
        stream4_jobs.run(ctx)


def test_the_digest_names_what_every_seed_has_to_give_alike():
    digest = stream4_jobs.slots_digest(_record())
    assert digest == {"devices_used": 4.0, "slots": 4, "stripes": 24, "tiles": 300, "turns": 84,
                      "tiles_by_slot": [75, 75, 75, 75]}
    assert stream4_jobs.slots_digest({})["tiles_by_slot"] == []


# ---- the whole cell, rehearsed -------------------------------------------------------------------


def test_a_rehearsal_on_four_virtual_devices_prints_a_well_formed_line():
    seed = 2**31 + 39
    argv = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", str(seed),
            "--seconds", "1", "--trace", "1", "--rehearse"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    proc = subprocess.run(argv, capture_output=True, text=True, env=env, timeout=900, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line, out = json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 1
    assert line["rehearsal"] is True and line["device"] == {**line["device"], "platform": "cpu", "count": 4}
    assert set(NEW) <= set(line["metrics"]) and "mash_kernel_ns_per_pair" not in line["metrics"]
    assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
    # 1,024 genomes in CPU tiles of 256: stripes of 4, 3, 2 and 1 tiles, a turn each on four slots
    assert line["metrics"]["stream_turn_pad_share"]["value"] == pytest.approx(37.5)
    assert line["metrics"]["stream_slot_balance"]["value"] == pytest.approx(100 * 2 / 3)
    assert 0 < line["metrics"]["stream_chip_occupancy"]["value"] <= 100
    # batch_jobs.run's own comparison (the Cdb of every job) and the four of the kind
    assert out.count("compare: ") == 5 and "WRONG" not in out and "job failed" not in out
    assert "stream: {'devices_used': 4.0, 'slots': 4, 'stripes': 4, 'tiles': 10, 'turns': 4" in out
    assert not os.path.exists(os.path.join(BENCH, ".work", f"{CELL}-{seed}"))

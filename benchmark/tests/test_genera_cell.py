"""The cell `gtdb_genera_6k.compare_greedy_loose`: its files are found by name
wherever later cells put theirs, its tables add up to ISSUE 54's sums, two
seeds lay out one slot table, the reference cuts every planted chain into its
planted clusters and finds them loose, the linkage's check passes the planted
answer and refuses a count that is off, a job whose record does not certify
the partition counts as failed, a program that cannot book the linkage fails
every job by the harness's own counters, the four readers read a made-up record and
give nothing on a record without the counters, the control fails every value
limit, the sweep finds equal work at toy size, and a rehearsal of the whole
cell prints a well-formed line."""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cells, check, control_genera, genera_jobs, greedy_jobs, margin_sweep_genera
from benchmark import reference as ref
from benchmark import reference_genera as rgen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "gtdb_genera_6k.compare_greedy_loose"
CONFIG = "gtdb_genera_6k"
TWIN = "gtdb_release_6k.compare_greedy"
NEW = ["primary_linkage_loose_share", "primary_mdb_noncluster_share", "secondary_greedy_reps_mean",
       "secondary_greedy_ms_per_call"]


def _reader(name):
    return cells.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


@pytest.fixture(scope="module")
def toy():
    loaded = cells.load_cell(CELL)
    cfg = loaded["config"]
    cfg = {**cfg, "data": {**cfg["data"], **cfg["rehearse"]}}
    data = loaded["generator"].generate(cfg["data"], 2**31 + 54)
    want = rgen.compare_genera(data.bottom, data.scaled, data.n_kmers, cfg["params"])
    return {"cfg": cfg, "mix": loaded["traffic"], "gen": loaded["generator"], "data": data,
            "want": want}


def _linkage(**over):
    """The record's `primary_linkage` as the streaming route books it on the full table."""
    did = {"genomes": 6144, "components": 3449, "singletons": 3232, "cliques": 176,
           "loose_components": 41, "rows_loose": 2560, "largest": 768, "edges_retained": 552_000,
           "edges_under_cutoff": 209_000, "edges_between_clusters": 343_000, "merges": 2639,
           "uncertified_merges": 0, "tree": "skipped"}
    did.update(over)
    return did


def _record(**over):
    call = {"rows": 160, "blocks": 2, "reps": 32, "device_calls": 8}
    rec = {"primary_linkage": _linkage(),
           "secondary_greedy_calls": [call] * 8 + [{"rows": 448, "blocks": 4, "reps": 128,
                                                    "device_calls": 56}],
           "phases": {"secondary/greedy_wait": {"seconds": 2.4, "self_seconds": 2.4, "calls": 9,
                                                "thread": "main"}}}
    rec.update(over)
    return rec


# ---- found by name -----------------------------------------------------------------------


def test_the_cell_is_found_by_name_wherever_later_cells_are_appended():
    loaded = cells.load_cell(CELL)
    assert loaded["cell"] == {**loaded["cell"], "config": CONFIG, "traffic": "compare_greedy_loose",
                              "chips": 1}
    assert loaded["traffic"]["kind"] == "genera_jobs" and hasattr(genera_jobs, "run")
    gen = loaded["generator"]
    assert all(hasattr(gen, f) for f in ("prepare", "generate", "plan", "write_workdir", "table_sums"))
    cfg, mix = loaded["config"], loaded["traffic"]
    assert cfg["generator"] == "planted_genera" and cfg["data"]["n"] == 6144 == 6 * 1024
    assert cfg["architecture"] is None
    assert cfg["reduced"] == ["n"] == list(cfg["reduced_why"]) and 300 <= cfg["rehearse"]["n"] <= 500
    # shapes, thresholds and the edges under the species are the release's, word for word
    release = cells.read_json(os.path.join(BENCH, "configs", "gtdb_release_6k.json"))
    assert cfg["params"] == release["params"]
    for key in ("s_bottom", "s_scaled", "kmer_size", "scale", "hash", "genome_length", "ani_edge",
                "lineage_size", "strain_size"):
        assert cfg["data"][key] == release["data"][key], key
    assert {"genera", "types", "chain edges", "bridges", "layout_seed", "accessory_max",
            "GTDB r220"} <= set(cfg["assumed"])
    assert len(cfg["guarantees"]) == 4 and "average linkage" in cfg["guarantees"][0]
    twin = cells.read_json(os.path.join(BENCH, "traffic", "compare_greedy.json"))
    assert mix["argv"] == twin["argv"] and mix["compare"] == twin["compare"]
    assert mix["limits"] == twin["limits"] and mix["limits_why"] == twin["limits_why"]
    assert mix["expect"] == {**twin["expect"],
                             "counters": twin["expect"]["counters"] + ["primary_linkage"],
                             "primary_linkage": {"uncertified_merges_at_most": 0,
                                                 "loose_components_at_least": 36}}
    spec = loaded["spec"]
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "config 5" in entry["source"] and "-pa 0.9" in entry["source"] and len(entry["source"]) <= 200
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert [w["name"] for w in spec["workloads"] if w["config"] == CONFIG] == [CELL]
    assert [m["name"] for m in cells.metrics_of(spec, CELL, "end_to_end")] == ["setup_s", "job_wall_s"]
    # every metric the twin reports, and the four of its own
    mine = {m["name"] for m in cells.metrics_of(spec, CELL, "per_layer")}
    assert mine == {m["name"] for m in cells.metrics_of(spec, TWIN, "per_layer")} | set(NEW)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:  # wherever they stand in the list
        assert by_name[name]["moves"] == "job_wall_s" and by_name[name]["workloads"][0] == CELL
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    assert [by_name[n]["layer"] for n in NEW] == ["primary compare"] * 2 + ["secondary compare"] * 2
    # appended where it was added: the cells that were there before it keep their places
    before = [w["name"] for w in spec["workloads"]]
    before = before[:before.index(CELL)]
    for m in spec["per_layer"] + spec["end_to_end"]:
        listed = m.get("workloads", [])
        if CELL in listed:
            assert [w for w in listed if w in before] == listed[:listed.index(CELL)]


@pytest.mark.parametrize("size", ["data", "rehearse"])
def test_the_tables_add_up_to_the_sums_the_issue_gives(size):
    loaded = cells.load_cell(CELL)
    cfg, gen = loaded["config"], loaded["generator"]
    params = {**cfg["data"], **(cfg["rehearse"] if size == "rehearse" else {})}
    sums = gen.table_sums(params)
    assert sums["genomes"] == params["n"]
    chains = [g for g in params["genera"] if len(g["chain"]) > 1]
    assert sums["loose_components"] == sum(g["count"] for g in chains) >= 9
    engine = [sum(params["types"][t]["species"]) for g in chains for t in g["chain"]
              for _ in range(g["count"]) if sum(params["types"][t]["species"]) > 32]
    assert len(engine) >= 3 and all(
        g["bridge_hashes"] > params["accessory_max"] * params["s_scaled"] for g in chains)
    if size == "data":
        assert sums == {"genomes": 6144, "clusters": 3505, "clusters_of_two_or_more": 273,
                        "species": 4112, "loose_components": 41, "rows_loose": 2560}
        kinds = {name: (sum(t["species"]), len(t["species"])) for name, t in params["types"].items()}
        assert kinds == {"R": (160, 32), "Rc": (160, 32), "C": (448, 128), "M": (24, 8), "S": (4, 2),
                         "T": (2, 1), "one": (1, 1)}
        assert params["types"]["R"]["species"] == params["types"]["Rc"]["species"]
        assert sorted(engine) == [160] * 8 + [448]  # 1,728 genomes, 384 representatives
        assert sums["genomes"] - sum(engine) - 3232 == 1184  # the batched route's
        # no two genomes of a cluster may tie in size: the accessory hashes have to allow it
        assert max(engine) <= params["accessory_max"] * params["s_scaled"] + 1


def test_two_seeds_lay_out_one_slot_table_and_differ_in_every_hash(toy):
    gen, params = toy["gen"], toy["cfg"]["data"]
    assert gen.plan(params).slot_table() == gen.plan(copy.deepcopy(params)).slot_table()
    other = gen.generate(params, 7)
    data = toy["data"]
    assert [len(s) for s in other.scaled] == [len(s) for s in data.scaled]
    assert np.array_equal(other.n_kmers, data.n_kmers) and np.array_equal(other.labels, data.labels)
    assert not set(other.scaled[0].tolist()) & set(data.scaled[0].tolist())
    assert all(np.array_equal(b, s[:1000]) for b, s in zip(data.bottom, data.scaled))
    sizes = np.bincount(data.primary_labels)
    for c in np.flatnonzero(sizes > 1):  # largest-first is one order inside a cluster
        assert len(set(data.n_kmers[data.primary_labels == c].tolist())) == sizes[c]
    assert len(data.links) == sum((len(g["chain"]) - 1) * g["count"] for g in params["genera"])
    assert all(data.genus[data.primary_labels == a][0] == data.genus[data.primary_labels == b][0]
               for a, b in data.links)


# ---- the reference ------------------------------------------------------------------------


def test_the_reference_cuts_every_chain_into_its_planted_clusters_and_finds_it_loose(toy):
    data, want, p = toy["data"], toy["want"], toy["cfg"]["params"]
    assert ref.partition_of(want["primary"]) == ref.partition_of(data.primary_labels)
    assert ref.partition_of(want["secondary"]) == ref.partition_of(data.labels)
    sums = toy["gen"].table_sums(toy["cfg"]["data"])
    did = want["linkage"]
    assert (did["loose_components"], did["rows_loose"]) == (sums["loose_components"], sums["rows_loose"])
    assert did["merges"] == sums["genomes"] - sums["clusters"]
    # most retained pairs join two clusters; some pairs under the cutoff do: the bridges
    assert did["edges_between_clusters"] > did["edges_retained"] / 2
    mash = want["mash"]
    across = data.primary_labels[mash["i"]] != data.primary_labels[mash["j"]]
    bridges = across & (mash["dist"] <= 1.0 - p["P_ani"])
    assert 4 * len(data.links) <= bridges.sum() < did["edges_under_cutoff"] / 20
    # the same partition from the sparse pairs alone, absent pairs at 1 (reference.py's own)
    edges = dict(zip(zip(mash["i"].tolist(), mash["j"].tolist()), mash["dist"].tolist()))
    assert set(ref.primary_partition(len(data.names), edges, 1.0 - p["P_ani"])) == \
        ref.partition_of(data.primary_labels)
    # two triangles joined by one edge: one loose component of six; a triangle: none
    i, j = np.array([0, 0, 1, 3, 3, 4, 2]), np.array([1, 2, 2, 4, 5, 5, 3])
    assert rgen.loose_components(7, i, j) == (1, 6) and rgen.loose_components(7, i[:3], j[:3]) == (0, 0)


# ---- the check ------------------------------------------------------------------------------


def _as_a_job_writes_it(want):
    got = copy.deepcopy(want)
    m = got["mash"]
    got["mash"] = {"i": np.concatenate([m["i"], m["j"]]), "j": np.concatenate([m["j"], m["i"]]),
                   "dist": np.concatenate([m["dist"], m["dist"]])}
    return got


def test_the_check_passes_the_planted_answer(toy, capsys):
    got = _as_a_job_writes_it(toy["want"])
    out = greedy_jobs.check_greedy(got, toy["data"], toy["cfg"]["params"], toy["mix"]["compare"],
                                   toy["mix"]["limits"], expected=toy["want"])
    out += genera_jobs.check_linkage({"primary_linkage": toy["want"]["linkage"]}, got, toy["want"], 0.25)
    assert len(out) == 9 + 7 and check.report(out)
    assert capsys.readouterr().out.count("compare: ") == 16


@pytest.mark.parametrize("count", genera_jobs.LINKAGE_COUNTS)
def test_the_check_refuses_a_record_whose_count_is_one_off(toy, count):
    did = {**toy["want"]["linkage"], count: toy["want"]["linkage"][count] + 1}
    out = genera_jobs.check_linkage({"primary_linkage": did}, None, toy["want"], 0.25)
    assert [c["what"].split(" ")[0] for c in out if not c["ok"]] == [f"primary_linkage.{count}"]
    assert all(not c["ok"] for c in genera_jobs.check_linkage({}, None, toy["want"], 0.25))


def test_the_check_refuses_a_job_that_merged_two_clusters_of_a_chain(toy):
    got = _as_a_job_writes_it(toy["want"])
    a, b = toy["data"].links[0]
    merged = np.isin(toy["data"].primary_labels, (a, b))
    got["primary"][merged] = got["primary"][merged][0]
    out = genera_jobs.check_linkage({"primary_linkage": toy["want"]["linkage"]}, got, toy["want"], 0.25)
    assert [c["what"][:9] for c in out if not c["ok"]] == ["Mdb pairs"]


# ---- a job's own record ------------------------------------------------------------------------


@pytest.mark.parametrize("linkage,fault", [
    (_linkage(), None),
    (_linkage(loose_components=36), None),
    (_linkage(uncertified_merges=1), "uncertified_merges = 1"),
    (_linkage(loose_components=35), "loose_components = 35"),
    ({"genomes": 6144, "components": 3449, "linkage_calls": 0}, "uncertified_merges = None"),
    (None, "loose_components = None"),
])
def test_a_job_whose_record_does_not_certify_the_partition_counts_as_failed(linkage, fault):
    expect = cells.load_cell(CELL)["traffic"]["expect"]
    faults = genera_jobs.linkage_faults({"primary_linkage": linkage} if linkage else {}, expect)
    assert (faults == []) if fault is None else any(fault in f for f in faults), faults
    assert genera_jobs.linkage_faults({}, {}) == []
    assert "primary_linkage" in expect["counters"]
    assert greedy_jobs.counter_faults({}, expect)[-1] == "the record holds no primary_linkage"


def test_a_program_whose_streaming_route_books_no_linkage_fails_every_job_and_ends_the_run(monkeypatch):
    """The parent of the PR that brought the cell: its jobs run, their records
    hold no `primary_linkage`, `expect.counters` fails each, and the run ends
    with no sound job. Nothing here reads the program's source."""
    from types import SimpleNamespace

    from benchmark import batch_jobs

    loaded = cells.load_cell(CELL)
    record = _record()
    del record["primary_linkage"]
    record["secondary_greedy_batched"] = {"clusters": 264}

    def window(ctx):
        ctx["generator"].prepare(ctx["config"], ctx["seed"], "nowhere")
        return {"correct": True, "attempted": 3, "failed": 0, "end_to_end": {"setup_s": 1.0},
                "run": {"jobs": [{"record": record, "wall_s": 1.0, "workdir": "nowhere"}] * 3,
                        "window_s": 3.0}}

    monkeypatch.setattr(batch_jobs, "run", window)
    planted = SimpleNamespace(prepare=lambda cfg, seed, out: {
        "workdir": out, "data": SimpleNamespace(names=[], labels=[])})
    ctx = {"config": loaded["config"], "traffic": loaded["traffic"], "generator": planted,
           "seed": 1, "rehearse": True}
    assert greedy_jobs.counter_faults(record, loaded["traffic"]["expect"]) == [
        "the record holds no primary_linkage"]
    with pytest.raises(SystemExit, match="no job of the window ran soundly"):
        genera_jobs.run(ctx)
    assert greedy_jobs.counters_unknown(loaded["traffic"]["expect"]) == []


# ---- the readers -----------------------------------------------------------------------------


def test_the_readers_on_a_record_of_the_cell():
    rec = _record()
    run = {"jobs": [{"wall_s": 11.0, "record": rec}, {"wall_s": 11.2, "record": rec}]}
    assert _reader("primary_linkage_loose_share").read(run) == pytest.approx(100 * 2560 / 6144)
    assert _reader("primary_mdb_noncluster_share").read(run) == pytest.approx(100 * 343 / 552)
    assert _reader("secondary_greedy_reps_mean").read(run) == pytest.approx(384 / 9)
    assert _reader("secondary_greedy_ms_per_call").read(run) == pytest.approx(2400 / 120)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_counters_gives_the_readers_nothing(name):
    dense = {"genomes": 5000, "components": 4000, "singletons": 3900, "cliques": 100,
             "linkage_calls": 0, "rows_linked": 0, "largest": 9, "tree": "skipped"}
    parent = {"primary_linkage": dense, "phases": {"job": {"seconds": 9.0, "self_seconds": 0.1}}}
    assert _reader(name).read({"jobs": [{"wall_s": 9.0, "record": parent}]}) is None
    assert _reader(name).read({"jobs": [{"wall_s": 1.0, "record": {"stages": {}}}]}) is None
    assert _reader(name).read({"jobs": []}) is None and _reader(name).read({}) is None


# ---- the control and the sweep ----------------------------------------------------------------


def test_the_control_fails_every_value_limit_and_the_sweep_finds_equal_work_at_toy_size(toy, capsys):
    assert control_genera.main(["--workload", CELL, "--seeds", "12", "--rehearse"]) == 0
    printed = capsys.readouterr().out
    wrong = [line for line in printed.splitlines() if line.endswith("WRONG")]
    assert len(wrong) >= 3 and "every value limit failed = True" in printed
    assert all(any(what in line for line in wrong) for what in ("Mash distance", "ANI error", "coverage error"))
    found = [margin_sweep_genera.sweep(toy["cfg"], toy["gen"], seed) for seed in (12, 2**31 + 13)]
    assert found[0]["work"] == found[1]["work"] and found[0]["moves"] != found[1]["moves"]
    sums = toy["gen"].table_sums(toy["cfg"]["data"])
    for f in found:
        assert f["margins"]["primary_wrong"] == f["margins"]["secondary_wrong"] == 0
        assert f["margins"]["primary_cut_gap"] > 0.005
        assert f["work"]["loose"] == (sums["loose_components"], sums["rows_loose"])
        assert f["chains"]["link_bridges"] >= 4 and 0.5 < f["chains"]["mdb_noncluster"] < 0.9
        assert 0.10 < f["chains"]["link_mean"][0] and f["chains"]["steps"][1][1] == 1.0
    assert found[0]["work"]["device_calls"] == sum(
        2 * e["blocks"] * e["chunks"] for e in found[0]["work"]["engine"])
    with pytest.raises(ValueError, match="one representative tile"):
        margin_sweep_genera.device_calls([{"reps": 513, "blocks": 5, "chunks": 1}])
    assert margin_sweep_genera.main(["--config", CONFIG, "--seeds", "3-4", "--rehearse"]) == 0
    assert "work differs from seed 3's on seeds: none" in capsys.readouterr().out


# ---- the whole cell, rehearsed -------------------------------------------------------------------


def test_a_rehearsal_prints_a_well_formed_line_with_every_metric_the_cpu_can_read():
    seed = 2**31 + 54
    argv = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", str(seed),
            "--seconds", "5", "--trace", "1", "--rehearse"]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(argv, capture_output=True, text=True, env={**env, "JAX_PLATFORMS": "cpu"},
                          timeout=900, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line, out = json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert set(NEW) <= set(line["metrics"])
    cfg = cells.load_cell(CELL)["config"]
    sums = cells.load_cell(CELL)["generator"].table_sums({**cfg["data"], **cfg["rehearse"]})
    assert line["metrics"]["primary_linkage_loose_share"]["value"] == pytest.approx(
        100 * sums["rows_loose"] / sums["genomes"])
    assert 50 < line["metrics"]["primary_mdb_noncluster_share"]["value"] < 90
    assert line["metrics"]["secondary_greedy_reps_mean"]["value"] == 40  # a 40-species cluster, thrice
    assert line["metrics"]["secondary_greedy_ms_per_call"]["value"] > 0
    assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
    # batch_jobs.run's own comparison, the nine of the greedy rule, the seven of the linkage
    assert out.count("compare: ") == 17 and "WRONG" not in out and "job failed" not in out
    assert "'uncertified_merges': 0" in out and f"'loose_components': {sums['loose_components']}" in out
    assert out.count("rehearsal: expected of the device path, not held here") == 2
    assert out.count("rehearsal: expected of the full table, not held here") == 1
    assert not os.path.exists(os.path.join(BENCH, ".work", f"{CELL}-{seed}"))

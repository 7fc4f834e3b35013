"""The cell `gtdb_release_6k.compare_greedy`: its files are found by name
wherever later cells put theirs, its size tables obey ISSUE 34's limits, its
reference equals a brute-force greedy over Python sets, its check passes the
planted answer and refuses a swapped representative, a missing or an added
Ndb pair and one hash count off, its control fails every value limit, its six
readers read a made-up record and give nothing on a record without the spans
and the counters, its roofline does not move with the tiles or the chunk plan
and cannot pass 100%, a job that did not run as the cell means counts as
failed, and a rehearsal of the whole cell prints a well-formed line."""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import cells, check, control_greedy, greedy_jobs, margin_sweep_release, roofline_greedy
from benchmark import reference as ref
from benchmark import reference_greedy as rg

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELL = "gtdb_release_6k.compare_greedy"
CONFIG = "gtdb_release_6k"
NEW = ["secondary_greedy_layout_s", "secondary_greedy_wait_s", "secondary_greedy_assign_s",
       "secondary_greedy_compared_share", "secondary_greedy_rep_pad_share",
       "secondary_greedy_roofline"]
V5E = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _reader(name):
    return cells.load_module(os.path.join(BENCH, "layer_metrics", name + ".py"))


@pytest.fixture(scope="module")
def toy():
    loaded = cells.load_cell(CELL)
    cfg = loaded["config"]
    cfg = {**cfg, "data": {**cfg["data"], **cfg["rehearse"]}}
    data = loaded["generator"].generate(cfg["data"], 2**31 + 21)
    want = rg.compare_greedy(data.bottom, data.scaled, data.n_kmers, cfg["params"])
    return {"cfg": cfg, "mix": loaded["traffic"], "gen": loaded["generator"], "data": data,
            "want": want}


def _call(**over):
    """One entry of `secondary_greedy_calls` as the engine books it on a TPU:
    a cluster of 576 in five blocks, four representatives at the end."""
    call = {"clusters": 1, "rows": 576, "blocks": 5, "block_rows": 128, "reps": 4, "rep_tile": 512,
            "rep_rows_shipped": 2560, "rep_rows_real": 13, "v_chunk": 262144, "chunks": 3,
            "extent": 718_000, "widths": 24576, "hashes": 11_750_000, "id_slots": 15_826_944,
            "device_calls": 30, "compared_pairs": 1900, "all_pairs": 165_600,
            "bytes_shipped": 63_307_776}
    call.update(over)
    return call


def _record(**over):
    phases = {"job": (14.0, 0.1), "secondary/pack": (3.0, 3.0), "secondary/greedy_layout": (0.9, 0.9),
              "secondary/greedy_wait": (4.2, 4.2), "secondary/greedy_assign": (0.6, 0.6),
              "secondary/wait": (0.3, 0.3)}
    rec = {"platform": "tpu", "device_kind": "TPU v5 lite", "n_devices": 1,
           "secondary_paths": {"greedy_matmul": 7, "one_shot_clusterlocal": 4},
           "secondary_greedy_calls": [_call(), _call(rows=48, blocks=1, reps=1, rep_rows_shipped=512,
                                                     rep_rows_real=0, chunks=1, extent=75_000,
                                                     widths=32768, hashes=980_000, id_slots=4_227_072,
                                                     device_calls=2, compared_pairs=47, all_pairs=1128,
                                                     bytes_shipped=16_908_288)],
           "secondary_greedy_batched": {"clusters": 376, "rows": 1536, "compared_pairs": 1300,
                                        "all_pairs": 6000},
           "secondary_calls": [{"rows_pad": 512, "calls": 4, "useful_pairs": 6000}],
           "phases": {k: {"seconds": s, "self_seconds": own, "calls": 1, "thread": "main"}
                      for k, (s, own) in phases.items()}}
    rec.update(over)
    return rec


# ---- found by name -----------------------------------------------------------------------


def test_the_cell_is_found_by_name_wherever_later_cells_are_appended():
    loaded = cells.load_cell(CELL)
    assert loaded["cell"] == {**loaded["cell"], "config": CONFIG, "traffic": "compare_greedy",
                              "chips": 1}
    assert loaded["traffic"]["kind"] == "greedy_jobs" and hasattr(greedy_jobs, "run")
    gen = loaded["generator"]
    assert all(hasattr(gen, f) for f in ("prepare", "generate", "plan", "write_workdir"))
    cfg, mix = loaded["config"], loaded["traffic"]
    assert cfg["generator"] == "planted_release" and cfg["data"]["n"] == 6144 == 6 * 1024
    assert cfg["reduced"] == ["n"] == list(cfg["reduced_why"]) and 300 <= cfg["rehearse"]["n"] <= 500
    # shapes and thresholds are those of the other deployments, word for word
    dense = cells.read_json(os.path.join(BENCH, "configs", "mags_5k.json"))
    assert cfg["params"] == dense["params"]
    for key in ("s_bottom", "s_scaled", "kmer_size", "scale", "hash", "genome_length"):
        assert cfg["data"][key] == dense["data"][key], key
    assert cfg["data"]["ani_edge"] == cells.read_json(
        os.path.join(BENCH, "configs", "ecoli_1k.json"))["data"]["ani_edge"]
    assert {"clusters", "layout_seed", "ani_edge", "accessory_max"} <= set(cfg["assumed"])
    assert len(cfg["guarantees"]) == 4 and "greedy assignment" in cfg["guarantees"][1]
    assert mix["argv"] == ["compare", "{workdir}", "--greedy_secondary_clustering",
                           "--streaming_primary", "--skip_plots"]
    assert mix["compare"] == ["primary", "secondary", "mdb", "ndb"]
    assert mix["expect"] == {"primary_estimator_resolved": "streaming_sort",
                             "secondary_path": "greedy_matmul",
                             "secondary_paths_only": ["greedy_matmul", "one_shot_clusterlocal"],
                             "counters": ["secondary_greedy_calls", "secondary_greedy_batched"]}
    stream = cells.read_json(os.path.join(BENCH, "traffic", "primary_stream.json"))["limits"]
    dense = cells.read_json(os.path.join(BENCH, "traffic", "compare_dense.json"))["limits"]
    assert mix["limits"] == {**dense, **stream}  # the streaming cell's Mash limit, the dense cell's others
    assert set(mix["limits_why"]) == set(mix["limits"])
    spec = loaded["spec"]
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cfg["source"] and entry["reduced"] == cfg["reduced"]
    assert "config 5" in entry["source"] and "--greedy_secondary_clustering" in entry["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert [w["name"] for w in spec["workloads"] if w["config"] == CONFIG] == [CELL]
    assert [m["name"] for m in cells.metrics_of(spec, CELL, "end_to_end")] == ["setup_s", "job_wall_s"]
    mine = {m["name"] for m in cells.metrics_of(spec, CELL, "per_layer")}
    assert set(NEW) <= mine
    assert {"host_rest_s", "primary_stage_s", "primary_prep_s", "primary_device_wait_s", "primary_post_s",
            "primary_linkage_s", "secondary_stage_s", "secondary_pack_s", "secondary_device_wait_s",
            "secondary_post_s", "secondary_checkpoint_s", "secondary_useful_pair_share",
            "load_sketches_s", "tables_s", "mash_kernel_ns_per_pair", "device_idle.batch",
            "idle_attributed", "host_unattributed_s", "compiles_in_window.batch", "evaluate_s"} <= mine
    assert not {"ring_collective_exposed", "secondary_chunked_roofline", "ingest_s", "filter_s"} & mine
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:  # wherever they stand in the list
        assert by_name[name]["moves"] == "job_wall_s" and CELL in by_name[name]["workloads"]
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py"))
    assert by_name["secondary_greedy_roofline"]["layer"] == "kernels"
    assert by_name["secondary_greedy_roofline"]["source"] == "device_trace"
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
    table = cfg[size]["clusters"]
    sizes = [c["size"] for c in table for _ in range(c["count"])]
    assert sum(sizes) == cfg[size]["n"] and all(sum(c["groups"]) == c["size"] for c in table)
    engine = [s for s in sizes if s > margin_sweep_release.ENGINE_OVER]
    batched = [s for s in sizes if 2 <= s <= margin_sweep_release.ENGINE_OVER]
    # at least two engine clusters, one of them of several blocks, and the batched route
    assert len(engine) >= 2 and max(engine) > margin_sweep_release.BLOCK and len(batched) >= 10
    if size == "data":
        assert max(engine) >= 512 and sum(engine) >= 768 and sum(batched) >= 1000
        assert all(len(c["groups"]) > 1 for c in table if c["size"] >= 64)
        # no two genomes of a cluster may tie in size: the accessory hashes have to allow it
        assert max(sizes) <= cfg["data"]["accessory_max"] * cfg["data"]["s_scaled"] + 1


# ---- the reference ------------------------------------------------------------------------


def _brute_force(scaled, n_kmers, k, s_ani, cov_thresh):
    """The greedy rule over Python sets, written apart from reference_greedy."""
    sets = [set(s.tolist()) for s in scaled]
    order = sorted(range(len(sets)), key=lambda t: (-int(n_kmers[t]), t))
    founders, label, rows = [], {}, set()
    for t in order:
        candidates = []
        for r in founders:
            shared = len(sets[t] & sets[r])
            cov_t, cov_r = shared / len(sets[t]), shared / len(sets[r])
            ani = max(cov_t, cov_r) ** (1.0 / k) if shared else 0.0
            rows.add((t, r))
            if ani >= s_ani and min(cov_t, cov_r) >= cov_thresh:
                candidates.append((-ani, founders.index(r), r))
        if candidates:
            label[t] = label[min(candidates)[2]]
        else:
            founders.append(t)
            label[t] = len(founders)
    return np.array([label[t] for t in range(len(sets))]), rows


def test_reference_equals_a_brute_force_greedy_over_sets_at_60_genomes(toy):
    data, p = toy["data"], toy["cfg"]["params"]
    sizes = np.bincount(data.primary_labels)
    pick = np.flatnonzero(np.isin(data.primary_labels, np.argsort(sizes)[::-1][:2]))[:60]
    assert len(pick) == 60 and len(np.unique(data.labels[pick])) >= 4
    scaled, n_kmers = [data.scaled[g] for g in pick], data.n_kmers[pick]
    labels, rows = rg.greedy_of_cluster(scaled, n_kmers, 21, p["S_ani"], p["cov_thresh"])
    want_labels, want_rows = _brute_force(scaled, n_kmers, 21, p["S_ani"], p["cov_thresh"])
    assert np.array_equal(labels, want_labels) and {(t, r) for t, r, *_ in rows} == want_rows
    assert len(rows) == len(want_rows) < 60 * 59 // 2 // 4  # a fraction of the all-pairs
    t, r, ani, cov_t, cov_r = rows[-1]
    shared = len(set(scaled[t].tolist()) & set(scaled[r].tolist()))
    assert (cov_t, cov_r) == (shared / len(scaled[t]), shared / len(scaled[r]))
    assert ani == max(cov_t, cov_r) ** (1.0 / 21)
    assert rg.shared_hashes(scaled[t], scaled[r]) == shared
    assert rg.shared_hashes(scaled[t], scaled[t][:0]) == 0
    # the whole job: the planted clusters and groups, one row a consumed pair, and no others
    want = toy["want"]
    assert ref.partition_of(want["primary"]) == ref.partition_of(data.primary_labels)
    assert ref.partition_of(want["secondary"]) == ref.partition_of(data.labels)
    rows = want["rows"]
    assert np.all(data.primary_labels[rows["q"]] == data.primary_labels[rows["r"]])
    assert np.all(data.n_kmers[rows["q"]] < data.n_kmers[rows["r"]])  # a representative was visited first
    assert len(set(zip(rows["q"].tolist(), rows["r"].tolist()))) == len(rows["q"])
    mash = want["mash"]
    assert np.all(mash["i"] < mash["j"]) and np.all(mash["dist"] < 1.0)
    i, j = int(mash["i"][0]), int(mash["j"][0])
    assert mash["dist"][0] == ref.mash_distance(ref.mash_jaccard(data.bottom[i], data.bottom[j], 1000), 21)


# ---- the check ------------------------------------------------------------------------------


def _check(toy, got):
    return greedy_jobs.check_greedy(got, toy["data"], toy["cfg"]["params"], toy["mix"]["compare"],
                                    toy["mix"]["limits"], expected=toy["want"])


def _as_a_job_writes_it(want):
    """The reference's answers in the form of a job's tables: both directions of the Mdb."""
    got = copy.deepcopy(want)
    m = got["mash"]
    got["mash"] = {"i": np.concatenate([m["i"], m["j"]]), "j": np.concatenate([m["j"], m["i"]]),
                   "dist": np.concatenate([m["dist"], m["dist"]])}
    return got


def test_the_check_passes_the_planted_answer(toy, capsys):
    out = _check(toy, _as_a_job_writes_it(toy["want"]))
    assert len(out) == 9 and check.report(out)
    assert capsys.readouterr().out.count("compare: ") == 9


def _swap_a_representative(got, toy):
    # a genome that met two representatives joins the other one's cluster
    rows, data = got["rows"], toy["data"]
    met, times = np.unique(rows["q"], return_counts=True)
    g = int(met[times >= 2][0])
    other = int(rows["r"][(rows["q"] == g) & (data.labels[rows["r"]] != data.labels[g])][0])
    got["secondary"][g] = got["secondary"][other]


def _drop_a_row(got, toy):
    got["rows"] = {k: v[1:] for k, v in got["rows"].items()}


def _add_a_row(got, toy):
    # a pair the scan never consumes: a representative against a later genome
    rows = got["rows"]
    got["rows"] = {"q": np.append(rows["q"], rows["r"][0]), "r": np.append(rows["r"], rows["q"][0]),
                   "ani": np.append(rows["ani"], rows["ani"][0]),
                   "cov_q": np.append(rows["cov_q"], rows["cov_r"][0]),
                   "cov_r": np.append(rows["cov_r"], rows["cov_q"][0])}


def _one_hash_off(got, toy):
    rows, data = got["rows"], toy["data"]
    q, r = int(rows["q"][5]), int(rows["r"][5])
    shared = rg.shared_hashes(data.scaled[q], data.scaled[r]) + 1
    cov_q, cov_r = shared / len(data.scaled[q]), shared / len(data.scaled[r])
    rows["ani"][5] = max(cov_q, cov_r) ** (1.0 / 21)


def _one_bottom_hash_off(got, toy):
    # one shared count fewer among 1,000: j from the distance, then the distance from j - 1/1000
    d = got["mash"]["dist"][3]
    j = 1.0 / (2.0 * np.exp(21 * d) - 1.0)
    got["mash"]["dist"][3] = ref.mash_distance(j - 1e-3, 21)


def _merge_two_clusters(got, toy):
    cluster = np.unique(got["primary"])
    got["primary"][got["primary"] == cluster[1]] = cluster[0]


@pytest.mark.parametrize("alter,wrong", [
    (_swap_a_representative, "genomes in a secondary cluster the reference does not have"),
    (_drop_a_row, "Ndb pairs the greedy scan does not consume, or missing"),
    (_add_a_row, "Ndb pairs the greedy scan does not consume, or missing"),
    (_one_hash_off, "largest ANI error"),
    (_one_bottom_hash_off, "largest Mash distance error"),
    (_merge_two_clusters, "genomes in a primary cluster the reference does not have"),
])
def test_the_check_refuses_an_altered_answer_by_the_comparison_it_touches(toy, alter, wrong):
    got = _as_a_job_writes_it(toy["want"])
    alter(got, toy)
    out = _check(toy, got)
    failed = [c["what"] for c in out if not c["ok"]]
    assert len(failed) == 1 and failed[0].startswith(wrong), failed
    if alter is _one_hash_off:  # one count in some 4,000: an ANI 2e-6 and more off, over the 1e-6
        value = next(c["value"] for c in out if not c["ok"])
        assert 1e-6 < value < 1e-4


def test_the_control_fails_every_value_limit_and_the_sweep_finds_equal_work_at_toy_size(toy, capsys):
    assert control_greedy.main(["--workload", CELL, "--seeds", "12", "--rehearse"]) == 0
    printed = capsys.readouterr().out
    wrong = [line for line in printed.splitlines() if line.endswith("WRONG")]
    assert len(wrong) == 3 and "every value limit failed = True" in printed
    assert all(any(w in line for w in ("Mash distance", "ANI error", "coverage error")) for line in wrong)
    out = greedy_jobs.check_greedy(None, toy["data"], toy["cfg"]["params"], toy["mix"]["compare"],
                                   toy["mix"]["limits"], lower_precision=True, expected=toy["want"])
    assert all(c["ok"] for c in out if c["limit"] == 0)  # the partitions and the pair set stand
    assert all(np.isfinite(c["value"]) and c["value"] > 10 * c["limit"] for c in out if c["limit"] > 0)
    found = [margin_sweep_release.sweep(toy["cfg"], toy["gen"], seed) for seed in (12, 2**31 + 13)]
    assert found[0]["work"] == found[1]["work"] and found[0]["moves"] != found[1]["moves"]
    for f in found:
        margins = f["margins"]
        assert margins["primary_wrong"] == margins["secondary_wrong"] == 0
        assert min(v for k, v in margins.items() if "wrong" not in k) > 0
        assert margins["nearest_decision"] == min(margins["ani_gap_own"], margins["ani_gap_other"])
    work = found[0]["work"]
    assert [e["rows"] for e in work["engine"]] == sorted((e["rows"] for e in work["engine"]),
                                                         reverse=True) or len(work["engine"]) == 2
    assert work["ndb_rows"] == sum(e["compared_pairs"] for e in work["engine"]) + \
        work["batched"]["compared_pairs"]
    assert margin_sweep_release.main(["--config", CONFIG, "--seeds", "3-4", "--rehearse"]) == 0
    assert "work differs from seed 3's on seeds: none" in capsys.readouterr().out


# ---- the readers -----------------------------------------------------------------------------


def test_the_readers_on_a_record_of_the_cell():
    rec = _record()
    run = {"jobs": [{"wall_s": 14.0, "record": rec}, {"wall_s": 14.2, "record": rec}]}
    assert _reader("secondary_greedy_layout_s").read(run) == 0.9
    assert _reader("secondary_greedy_wait_s").read(run) == 4.2
    assert _reader("secondary_greedy_assign_s").read(run) == 0.6
    # both routes: what greedy spares of the all-pairs
    assert _reader("secondary_greedy_compared_share").read(run) == pytest.approx(
        100 * (1900 + 47 + 1300) / (165_600 + 1128 + 6000))
    assert _reader("secondary_greedy_rep_pad_share").read(run) == pytest.approx(100 * (1 - 13 / 3072))
    # the accepted readers keep to their own spans: the one-shot call's wait is not the engine's
    assert _reader("secondary_device_wait_s").read(run) == 0.3
    assert _reader("secondary_pack_s").read(run) == 3.0
    # the roofline: the device operations that start inside the engine's wait spans, and no others
    host = [("drep:job", 0.0, 14e9), ("drep:secondary/greedy_wait", 1e9, 1e9),
            ("drep:secondary/greedy_wait", 3e9, 1e9), ("drep:secondary/wait", 5e9, 1e9)]
    device = [("fusion.1", 1.1e9, 3e8), ("fusion.2", 3.5e9, 1e8), ("fusion.3", 5.5e9, 5e8),
              ("mash", 1e8, 5e8), ("copy", 2.5e9, 1e8)]
    traced = {"jobs": run["jobs"][:1], "peaks": V5E,
              "trace": {"events": {"host": host, "devices": {"/device:TPU:0": device}}}}
    least, bound = roofline_greedy.greedy_least_seconds(rec["secondary_greedy_calls"], V5E)
    share = _reader("secondary_greedy_roofline").read(traced)
    assert share == pytest.approx(100.0 * least / 0.4) and bound in ("int8", "hbm")
    assert 0 < share < 100.0


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_spans_and_the_counters_gives_the_readers_nothing(name):
    parent = {k: v for k, v in _record().items() if "greedy" not in k}
    parent["phases"] = {k: v for k, v in parent["phases"].items() if "greedy" not in k}
    events = {"host": [("drep:secondary/wait", 0.0, 1e9)],
              "devices": {"/device:TPU:0": [("fusion", 1.0, 5.0)]}}
    run = {"jobs": [{"wall_s": 14.0, "record": parent}], "peaks": V5E, "trace": {"events": events}}
    assert _reader(name).read(run) is None
    assert _reader(name).read({"jobs": [{"wall_s": 1.0, "record": {"stages": {}}}]}) is None
    assert _reader(name).read({"jobs": []}) is None and _reader(name).read({}) is None
    if name == "secondary_greedy_roofline":
        job = {"wall_s": 14.0, "record": _record()}
        spans = {"host": [("drep:secondary/greedy_wait", 0.0, 1e9)], "devices": events["devices"]}
        assert _reader(name).read({"jobs": [job], "peaks": V5E, "trace": {"events": spans}}) > 0
        assert _reader(name).read({"jobs": [job], "peaks": V5E, "trace": None}) is None
        assert _reader(name).read({"jobs": [job], "peaks": None, "trace": {"events": spans}}) is None
        assert _reader(name).read({"jobs": [job], "peaks": V5E, "trace": {"events": events}}) is None


def test_roofline_counts_the_work_by_hand_and_never_the_layout():
    call = _call()
    # 1,900 consumed pairs over 718,000 ids; 576 indicators written and read, ids, counts
    assert roofline_greedy.greedy_macs(call) == 1900 * 718_000
    by_hand = 2 * 576 * 718_000 + 4 * 11_750_000 + 4 * 1900
    assert roofline_greedy.greedy_bytes(call) == by_hand == 874_143_600
    seconds, bound = roofline_greedy.greedy_least_seconds([call], V5E)
    assert bound == "hbm" and seconds == pytest.approx(by_hand / 819e9)
    assert 2 * 1900 * 718_000 / 393e12 < seconds
    # another tiling of the same work: rep tile, block, chunk plan, padding, what was shipped
    retiled = _call(rep_tile=128, rep_rows_shipped=640, block_rows=512, blocks=2, v_chunk=65536,
                    chunks=11, widths=90112, id_slots=10**9, bytes_shipped=4 * 10**9,
                    device_calls=10**6, rep_rows_real=7)
    assert roofline_greedy.greedy_least_seconds([retiled], V5E) == (seconds, bound)
    assert roofline_greedy.greedy_bytes(retiled) == by_hand
    # all-pairs would be bound by the multiply-accumulates
    assert roofline_greedy.greedy_least_seconds(
        [_call(rows=100, compared_pairs=10**9, hashes=2_000_000)], V5E)[1] == "int8"


def test_the_roofline_share_cannot_pass_100_when_the_device_takes_the_least_seconds():
    rec = _record()
    least, _ = roofline_greedy.greedy_least_seconds(rec["secondary_greedy_calls"], V5E)
    for slower in (1.0, 1.5, 40.0):
        events = {"host": [("drep:secondary/greedy_wait", 0.0, 60e9)],
                  "devices": {"/device:TPU:0": [("fusion", 1e6, least * slower * 1e9)]}}
        share = _reader("secondary_greedy_roofline").read(
            {"jobs": [{"wall_s": 14.0, "record": rec}], "peaks": V5E, "trace": {"events": events}})
        assert share == pytest.approx(100.0 / slower) and share <= 100.0 + 1e-9


# ---- a job's own record ------------------------------------------------------------------------


@pytest.mark.parametrize("record,fault", [
    (_record(), None),
    (_record(secondary_paths={"greedy_gather": 7, "one_shot_clusterlocal": 4}), "'greedy_matmul' did not serve"),
    (_record(secondary_paths={"greedy_gather": 7, "one_shot_clusterlocal": 4}), "['greedy_gather'], outside"),
    (_record(secondary_paths={"greedy_matmul": 7, "one_shot_clusterlocal": 4, "matmul_chunked": 1}),
     "['matmul_chunked'], outside"),
    (_record(secondary_paths={"greedy_matmul": 7, "one_shot_clusterlocal": 4, "cpu_tiles": 1}),
     "secondary served by ['cpu_tiles']"),
    (_record(secondary_greedy_calls=[]), "holds no secondary_greedy_calls"),
    (_record(secondary_greedy_batched={}), "holds no secondary_greedy_batched"),
    (_record(fault_tolerance={"retries": 1}), "did not run where it was meant to"),
    (_record(platform="cpu"), "record says platform"),
])
def test_a_job_that_did_not_run_as_the_cell_means_counts_as_failed(record, fault):
    from benchmark import batch_jobs

    expect = cells.load_cell(CELL)["traffic"]["expect"]
    faults = (batch_jobs.record_faults(record, DEVICE, expect, "streaming_sort")
              + greedy_jobs.route_faults(record, expect) + greedy_jobs.counter_faults(record, expect))
    assert (faults == []) if fault is None else any(fault in f for f in faults), faults
    assert any("resolved" in f for f in batch_jobs.record_faults(record, DEVICE, expect, "sort"))


def test_the_digest_names_what_every_seed_has_to_give_alike():
    digest = greedy_jobs.greedy_digest(_record())
    assert digest["clusters"] == 2 and digest["rows"] == [576, 48] and digest["chunks"] == [3, 1]
    assert digest["batched"]["rows"] == 1536 and digest["hashes"] == 11_750_000 + 980_000
    assert greedy_jobs.greedy_digest({})["clusters"] == 0


# ---- the whole cell, rehearsed -------------------------------------------------------------------


def test_a_rehearsal_prints_a_well_formed_line_with_every_metric_the_cpu_can_read():
    seed = 2**31 + 34
    argv = [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", str(seed),
            "--seconds", "5", "--trace", "1", "--rehearse"]
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(argv, capture_output=True, text=True, env={**env, "JAX_PLATFORMS": "cpu"},
                          timeout=900, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line, out = json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    # no published peak for a CPU: no roofline; everything else reads
    assert set(NEW) - {"secondary_greedy_roofline"} <= set(line["metrics"])
    assert "secondary_greedy_roofline" not in line["metrics"]
    assert line["metrics"]["compiles_in_window.batch"]["value"] == 0
    assert 0 < line["metrics"]["secondary_greedy_compared_share"]["value"] < 50
    assert 0 < line["metrics"]["secondary_greedy_rep_pad_share"]["value"] <= 100
    assert line["metrics"]["secondary_greedy_wait_s"]["value"] > 0
    # batch_jobs.run's own comparison (the Cdb of every job) and the nine of the greedy rule
    assert out.count("compare: ") == 10 and "WRONG" not in out
    assert out.count("rehearsal: expected of the device path, not held here") == 2
    assert "greedy: {'clusters': 2" in out and "job failed" not in out
    assert not os.path.exists(os.path.join(BENCH, ".work", f"{CELL}-{seed}"))

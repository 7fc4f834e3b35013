"""ISSUE 34: the deployment `gtdb_release_6k` at its rehearsal size on the CPU.
Whole `compare --greedy_secondary_clustering --streaming_primary` jobs against
the plain reference of the greedy rule (benchmark/reference_greedy.py) on the
engine's gather route and on its matmul route, what such a job's record books,
and that the seed draws the hashes and nothing else: four seeds, the same
work entry for entry."""

import json
import os
import shutil

import numpy as np
import pandas as pd
import pytest

from benchmark import cells, check, greedy_jobs, margin_sweep_release
from benchmark import reference_greedy as rg
from drep_tpu.cluster.controller import SMALL_CLUSTER_MAX

CELL = "gtdb_release_6k.compare_greedy"
SEED = 2**31 + 34
SPANS = ("secondary/pack", "secondary/greedy_layout", "secondary/greedy_wait",
         "secondary/greedy_assign")
# what the seed may move of a cluster's entry, each by under 1% (PERF.md section 4)
SEED_MAY_MOVE = ("extent", "hashes", "bytes_shipped", "id_slots")


@pytest.fixture(scope="module")
def cell():
    loaded = cells.load_cell(CELL)
    cfg = loaded["config"]
    loaded["config"] = {**cfg, "data": {**cfg["data"], **cfg["rehearse"]}}
    return loaded


def _job(cell, wd: str, *more: str) -> dict:
    """One whole job of the cell's own argv on a planted workdir: its record,
    its answers, its table sizes."""
    from drep_tpu import controller

    controller.main([a.replace("{workdir}", wd) for a in cell["traffic"]["argv"]] + list(more))
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        record = json.load(f)
    tables = os.path.join(wd, "data_tables")
    return {"wd": wd, "record": record,
            "ndb": pd.read_csv(os.path.join(tables, "Ndb.csv")),
            "cdb": pd.read_csv(os.path.join(tables, "Cdb.csv"))}


@pytest.fixture(scope="module")
def planted(cell, tmp_path_factory):
    out = str(tmp_path_factory.mktemp("greedy_cell"))
    prepared = cell["generator"].prepare(cell["config"], SEED, out)
    want = rg.compare_greedy(prepared["data"].bottom, prepared["data"].scaled,
                             prepared["data"].n_kmers, cell["config"]["params"])
    return {**prepared, "out": out, "want": want}


@pytest.fixture(scope="module")
def jobs(cell, planted):
    """The same planted collection through both routes of the engine (the
    matmul route's job with its event log on)."""
    from drep_tpu.utils import telemetry

    done = {}
    for route in ("greedy_gather", "greedy_matmul"):
        wd = os.path.join(planted["out"], route)
        shutil.copytree(planted["workdir"], wd)
        with pytest.MonkeyPatch.context() as mp:
            if route == "greedy_matmul":
                # the chip's route: one device (conftest gives the CPU eight), blocks of 128
                mp.setenv("DREP_TPU_GREEDY_MATMUL", "1")
                done[route] = _job(cell, wd, "--mesh_shape", "1", "--events", "on")
                telemetry.configure()
            else:
                done[route] = _job(cell, wd)
    return done


@pytest.mark.parametrize("route", ["greedy_gather", "greedy_matmul"])
def test_a_whole_job_equals_the_reference_of_the_greedy_rule(cell, planted, jobs, route):
    cfg, mix, data = cell["config"], cell["traffic"], planted["data"]
    assert mix["argv"][2:] == ["--greedy_secondary_clustering", "--streaming_primary", "--skip_plots"]
    job = jobs[route]
    # partitions, the Ndb's pair set and every value, by the cell's own comparison
    out = greedy_jobs.check_greedy(greedy_jobs.read_answers(job["wd"], data.names), data,
                                   cfg["params"], mix["compare"], mix["limits"],
                                   expected=planted["want"])
    assert len(out) == 9 and all(c["ok"] for c in out), [c for c in out if not c["ok"]]
    ani = next(c for c in out if c["what"].startswith("largest ANI error"))
    assert 0 < ani["value"] < 1e-6  # float32 against float64: compared, and not equal by copy


def test_both_routes_give_the_same_cdb_and_consume_the_same_pairs(jobs):
    gather, matmul = jobs["greedy_gather"], jobs["greedy_matmul"]
    assert gather["cdb"].equals(matmul["cdb"])
    pairs = [set(zip(j["ndb"]["querry"], j["ndb"]["reference"])) for j in (gather, matmul)]
    assert pairs[0] == pairs[1] and len(pairs[0]) == len(gather["ndb"])
    np.testing.assert_allclose(gather["ndb"]["ani"], matmul["ndb"]["ani"], atol=1e-6)


@pytest.mark.parametrize("route", ["greedy_gather", "greedy_matmul"])
def test_the_record_says_which_route_served_what(planted, jobs, route):
    data, rec, ndb = planted["data"], jobs[route]["record"], jobs[route]["ndb"]
    sizes = np.bincount(data.primary_labels)
    engine = sizes[sizes > SMALL_CLUSTER_MAX]
    small = sizes[(sizes > 1) & (sizes <= SMALL_CLUSTER_MAX)]
    assert len(engine) == 2 and engine.max() > 128 and len(small) >= 10  # both routes have work
    other = ({"greedy_gather", "greedy_matmul"} - {route}).pop()
    assert rec["secondary_paths"][route] == len(engine) and other not in rec["secondary_paths"]
    assert rec["secondary_paths"]["one_shot_clusterlocal"] >= 1
    for name in SPANS:
        assert rec["phases"][name]["seconds"] > 0, name
    calls = rec["secondary_greedy_calls"]
    assert sorted(c["rows"] for c in calls) == sorted(engine.tolist())
    groups = {int(c): len(np.unique(data.labels[data.primary_labels == c]))
              for c in np.flatnonzero(sizes > SMALL_CLUSTER_MAX)}
    assert sorted(c["reps"] for c in calls) == sorted(groups.values())
    for c in calls:
        assert c["clusters"] == 1 and c["blocks"] == -(-c["rows"] // c["block_rows"])
        assert c["all_pairs"] == c["rows"] * (c["rows"] - 1) // 2 > c["compared_pairs"] > 0
        if route == "greedy_matmul":
            # sized tiles (ISSUE 55): a cluster's first block meets no representative and makes
            # no call against any, every later one meets a handful in one tile of 128 rows
            assert c["blocks_without_reps"] == 1 and c["rep_tile"] == 512
            assert c["rep_rows_shipped"] == (c["blocks"] - 1) * 128 >= c["rep_rows_real"]
            assert c["device_calls"] == (2 * c["blocks"] - 1) * c["chunks"]
        else:
            assert c["blocks_without_reps"] == 0
            assert c["rep_rows_shipped"] >= c["blocks"] * c["rep_tile"] > c["rep_rows_real"]
        assert 0 < c["hashes"] <= c["id_slots"] and c["bytes_shipped"] == 4 * c["id_slots"]
        assert c["extent"] > 0 and c["device_calls"] >= c["blocks"]
        assert (c["v_chunk"] > 0 and c["chunks"] > 0) == (route == "greedy_matmul")
    # a span a block of the engine
    assert rec["phases"]["secondary/greedy_wait"]["calls"] == sum(c["blocks"] for c in calls)
    if route == "greedy_matmul":  # the job whose event log is on: the span says what it computed against
        from tools import trace_report

        spans, _ = trace_report.pair_spans(
            trace_report.load_events(os.path.join(jobs[route]["wd"], "log"))["events"])
        waits = [sp["args"] for sp in spans if sp["ev"] == "secondary/greedy_wait"]
        assert sum(w["rep_pad"] for w in waits) == sum(c["rep_rows_shipped"] for c in calls)
        assert sum(w["reps"] for w in waits) == sum(c["rep_rows_real"] for c in calls)
        assert {w["rep_pad"] for w in waits if w["reps"] == 0} == {0}
        assert {w["rep_pad"] for w in waits if w["reps"] > 0} == {128}
    # the pairs booked are the Ndb's rows: the engine's clusters and the batched route's
    batched = rec["secondary_greedy_batched"]
    assert (batched["clusters"], batched["rows"]) == (len(small), small.sum())
    assert batched["all_pairs"] == sum(m * (m - 1) // 2 for m in small)
    assert sum(c["compared_pairs"] for c in calls) + batched["compared_pairs"] == len(ndb)
    assert rec["stages"]["secondary_compare"]["pairs"] == len(ndb)
    by_cluster = ndb.groupby("primary_cluster")["querry"].size()
    assert {c["compared_pairs"] for c in calls} <= set(by_cluster.tolist())
    assert by_cluster.max() == max(c["compared_pairs"] for c in calls)


@pytest.mark.parametrize("route", ["greedy_gather", "greedy_matmul"])
def test_the_pack_spans_and_the_record_say_how_the_engines_clusters_were_ranked(planted, jobs, route):
    """ISSUE 44: the engine's `secondary/pack` span carries `hashes=`, `path=`
    and `workers=` (what `rank_route` names for the job's `-p`), one span a
    cluster, and the record's `secondary_pack` adds them up: native wherever
    the library is there. The batched route's cluster-local pack books none."""
    from drep_tpu import native
    from drep_tpu.ops.minhash import rank_route
    from tools import trace_report

    data, rec = planted["data"], jobs[route]["record"]
    sizes = np.bincount(data.primary_labels)
    engine = np.flatnonzero(sizes > SMALL_CLUSTER_MAX)
    hashes = [sum(len(data.scaled[g]) for g in np.flatnonzero(data.primary_labels == c)) for c in engine]
    routes = [rank_route(h, 6) for h in hashes]  # the cell's argv leaves `-p` at the CLI's 6
    is_native = native.get_library() is not None
    assert rec["secondary_pack"] == {"calls": len(engine), "native_calls": len(engine) * is_native,
                      "rows": int(sizes[engine].sum()), "hashes": sum(hashes),
                      "threads": max(t for _, t in routes)}
    if route == "greedy_matmul":  # the job whose event log is on
        spans, _ = trace_report.pair_spans(
            trace_report.load_events(os.path.join(jobs[route]["wd"], "log"))["events"])
        ranked = [sp["args"] for sp in spans if sp["ev"] == "secondary/pack" and "path" in sp["args"]]
        want = [{"hashes": h, "path": p, "workers": t} for h, (p, t) in zip(hashes, routes)]
        assert sorted(ranked, key=str) == sorted(want, key=str)


def test_the_control_fails_the_limits_and_a_swapped_order_fails_the_pair_set(cell, planted):
    cfg, mix, data, want = cell["config"], cell["traffic"], planted["data"], planted["want"]
    control = greedy_jobs.check_greedy(None, data, cfg["params"], mix["compare"], mix["limits"],
                                       lower_precision=True, expected=want)
    assert not check.report(control)
    assert all(not c["ok"] for c in control if c["limit"] > 0)  # every value limit
    # the reference itself, as an answer, passes; with two visits of the largest cluster
    # swapped it founds another representative and consumes other pairs
    same = greedy_jobs.check_greedy(want, data, cfg["params"], mix["compare"], mix["limits"],
                                    expected=want)
    assert all(c["ok"] for c in same)
    big = np.flatnonzero(want["primary"] == np.bincount(want["primary"]).argmax())
    order = rg.visiting_order(data.n_kmers[big])
    order[0], order[1] = order[1], order[0]
    labels, made = rg.greedy_of_cluster([data.scaled[g] for g in big], data.n_kmers[big], 21,
                                        0.95, 0.1, order=order)
    rows = want["rows"]
    keep = ~np.isin(rows["q"], big)
    table = np.array([(big[t], big[r], a, cq, cr) for t, r, a, cq, cr in made])
    swapped = {**want, "rows": {
        key: np.concatenate([rows[key][keep], table[:, i].astype(rows[key].dtype)])
        for i, key in enumerate(("q", "r", "ani", "cov_q", "cov_r"))}}
    out = greedy_jobs.check_greedy(swapped, data, cfg["params"], mix["compare"], mix["limits"],
                                   expected=want)
    wrong = [c["what"] for c in out if not c["ok"]]
    assert len(wrong) == 1 and wrong[0].startswith("Ndb pairs the greedy scan does not consume")


def test_four_seeds_do_the_same_work(cell, jobs, tmp_path):
    """The seed draws the hash values: counters, table sizes and span calls
    of whole jobs are equal entry for entry."""
    found = [jobs["greedy_gather"]]
    for seed in (0, 7, 3000003407):
        prepared = cell["generator"].prepare(cell["config"], seed, str(tmp_path / str(seed)))
        found.append(_job(cell, prepared["workdir"]))
    first = found[0]
    for job in found[1:]:
        for mine, theirs in zip(job["record"]["secondary_greedy_calls"],
                                first["record"]["secondary_greedy_calls"], strict=True):
            assert {k: v for k, v in mine.items() if k not in SEED_MAY_MOVE} == \
                {k: v for k, v in theirs.items() if k not in SEED_MAY_MOVE}
            for key in SEED_MAY_MOVE:
                assert mine[key] == pytest.approx(theirs[key], rel=0.01), key
        assert job["record"]["secondary_greedy_batched"] == first["record"]["secondary_greedy_batched"]
        assert job["record"]["secondary_paths"] == first["record"]["secondary_paths"]
        assert job["record"]["secondary_calls"] == first["record"]["secondary_calls"]
        assert (len(job["ndb"]), len(job["cdb"])) == (len(first["ndb"]), len(first["cdb"]))
        assert job["cdb"]["secondary_cluster"].tolist() == first["cdb"]["secondary_cluster"].tolist()
        calls = [{name: ph["calls"] for name, ph in j["record"]["phases"].items()}
                 for j in (job, first)]
        assert calls[0] == calls[1]
    assert len({j["ndb"]["ani"].sum() for j in found}) == len(found)  # other hashes, other values


def test_the_slot_table_is_byte_equal_across_seeds_and_the_sizes_are_the_tables(cell):
    gen, params = cell["generator"], cell["config"]["data"]
    laid = gen.plan(params)
    datas = [gen.generate(params, seed) for seed in (1, 2**31 + 2)]
    for data in datas:
        table = np.stack([data.primary_labels, data.labels, data.n_kmers]).astype(np.int64).tobytes()
        assert table == laid.slot_table()
        assert [len(s) for s in data.scaled] == [len(s) for s in datas[0].scaled]
    assert not np.array_equal(datas[0].scaled[0], datas[1].scaled[0])  # the seed draws the hashes
    sizes = np.bincount(laid.cluster)
    want = [c["size"] for c in params["clusters"] for _ in range(c["count"])]
    assert sorted(sizes.tolist()) == sorted(want) and sizes.sum() == params["n"]
    for entry in params["clusters"]:
        ci = int(np.flatnonzero(sizes == entry["size"])[0])
        groups = np.bincount(laid.group[laid.cluster == ci])
        assert sorted(groups[groups > 0].tolist()) == sorted(entry["groups"])
    # inside a cluster no two genomes tie in n_kmers: largest-first is one order
    for ci in np.flatnonzero(sizes > 1):
        inside = laid.n_kmers[laid.cluster == ci]
        assert len(np.unique(inside)) == len(inside)
    # scattered over the input order, not listed by cluster
    big = np.flatnonzero(laid.cluster == sizes.argmax())
    assert big.max() - big.min() > 2 * len(big)


def test_the_sweep_s_geometry_is_the_program_s_at_toy_size(cell, planted):
    """margin_sweep_release.py copies the chunk rule; here it is held to
    ops/containment.py::VocabChunkGeometry on the planted clusters."""
    from drep_tpu.ops.containment import VocabChunkGeometry, pack_scaled_sketches
    from drep_tpu.ops.rangepart import vocab_extent

    data = planted["data"]
    sizes = np.bincount(data.primary_labels)
    for ci in np.flatnonzero(sizes > SMALL_CLUSTER_MAX):
        scaled = [data.scaled[g] for g in np.flatnonzero(data.primary_labels == ci)]
        packed = pack_scaled_sketches(scaled, [str(i) for i in range(len(scaled))])
        geom = VocabChunkGeometry(packed.ids, max_rows_per_call=512)
        mine = margin_sweep_release.engine_geometry(scaled)
        assert geom.v_chunk == margin_sweep_release.V_CHUNK
        assert (mine["extent"], mine["chunks"], mine["widths"]) == \
            (vocab_extent(packed.ids), geom.n_chunks, geom.widths)
    # several chunks, where the vocabulary is made to span them
    rng = np.random.default_rng(3)
    wide = [np.unique(rng.integers(0, 2**62, size=9000, dtype=np.uint64)) for _ in range(40)]
    packed = pack_scaled_sketches(wide, [str(i) for i in range(40)])
    geom = VocabChunkGeometry(packed.ids, max_rows_per_call=512)
    mine = margin_sweep_release.engine_geometry(wide)
    assert geom.n_chunks == mine["chunks"] == 2 and geom.widths == mine["widths"]
    assert margin_sweep_release.ENGINE_OVER == SMALL_CLUSTER_MAX

"""ISSUE 54: the streaming primary books the linkage it ran in the job's
record (`primary_linkage`, as the dense route does), so that a cell can hold it
to "the partition is what a from-scratch average linkage gives". On planted
genera (chains of primary clusters: components of the cutoff graph that are no
cliques) the streaming route, the dense route and the benchmark's reference
give one partition, the planted one."""

import json
import os
import shutil

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEYS = {"genomes", "components", "singletons", "cliques", "loose_components", "rows_loose", "largest",
        "edges_retained", "edges_under_cutoff", "edges_between_clusters", "merges",
        "uncertified_merges"}


@pytest.fixture(scope="module")
def genera(tmp_path_factory):
    """The toy table of `gtdb_genera_6k`, planted as a work directory."""
    from benchmark import cells

    cfg = cells.read_json(os.path.join(REPO, "benchmark", "configs", "gtdb_genera_6k.json"))
    cfg["data"].update(cfg["rehearse"])
    gen = cells.load_module(os.path.join(REPO, "benchmark", "generators", "planted_genera.py"))
    planted = gen.prepare(cfg, 54, str(tmp_path_factory.mktemp("genera")))
    return {**planted, "params": cfg["params"], "sums": gen.table_sums(cfg["data"])}


def _job(genera, tmp_path, *flags):
    from drep_tpu import controller

    wd = str(tmp_path / "wd")
    shutil.copytree(genera["workdir"], wd)
    controller.main(["compare", wd, "--skip_plots", "--SkipSecondary", *flags])
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        rec = json.load(f)
    import pandas as pd

    cdb = pd.read_csv(os.path.join(wd, "data_tables", "Cdb.csv")).set_index("genome")
    return rec, cdb.loc[genera["data"].names, "primary_cluster"].to_numpy()


def _partition(labels):
    groups = {}
    for g, c in enumerate(labels):
        groups.setdefault(int(c), []).append(g)
    return {frozenset(v) for v in groups.values()}


def test_the_streaming_route_books_the_linkage_and_finds_the_planted_partition(genera, tmp_path):
    from benchmark import reference as ref
    from benchmark import reference_genera as rgen
    from benchmark import reference_greedy as rg

    data, p, sums = genera["data"], genera["params"], genera["sums"]
    rec, primary = _job(genera, tmp_path, "--streaming_primary", "--streaming_block", "128")
    did = rec["primary_linkage"]
    assert set(did) == KEYS | {"tree"} and did["tree"] == "skipped"
    assert _partition(primary) == _partition(data.primary_labels)
    # what the record says is what the reference counts from the exact pairs
    labels, mash = rg.primary(data.bottom, 1000, 21, 1.0 - p["P_ani"])
    assert _partition(labels) == _partition(data.primary_labels)
    want = rgen.linkage_counts(len(data.names), mash, labels, p)
    assert {k: did[k] for k in want} == want
    assert (did["loose_components"], did["rows_loose"]) == (sums["loose_components"], sums["rows_loose"])
    assert did["uncertified_merges"] == 0 and did["merges"] == sums["genomes"] - sums["clusters"]
    assert did["genomes"] == sums["genomes"] and did["largest"] == 150
    assert did["components"] == did["singletons"] + did["cliques"] + did["loose_components"]
    assert did["edges_between_clusters"] > did["edges_retained"] / 2  # an Mdb of pairs that join no cluster
    # reference.py's own partition from the sparse pairs, absent pairs at 1
    edges = dict(zip(zip(mash["i"].tolist(), mash["j"].tolist()), mash["dist"].tolist()))
    assert set(ref.primary_partition(len(data.names), edges, 1.0 - p["P_ani"])) == _partition(primary)
    # the span carries the pass's own seconds
    assert rec["phases"]["primary/linkage"]["calls"] == 1


def test_the_dense_route_links_the_loose_components_and_agrees(genera, tmp_path):
    data, sums = genera["data"], genera["sums"]
    rec, primary = _job(genera, tmp_path, "--mesh_shape", "1")
    did = rec["primary_linkage"]
    assert _partition(primary) == _partition(data.primary_labels)
    assert did["linkage_calls"] == sums["loose_components"] > 0
    assert did["rows_linked"] == sums["rows_loose"]
    assert did["cliques"] < did["components"] - did["singletons"]
    assert "uncertified_merges" not in did  # every pair is observed there


def _python_path(monkeypatch, *args):
    from drep_tpu.ops.linkage import sparse_average_linkage

    monkeypatch.setenv("DREP_TPU_NO_NATIVE", "1")
    out = sparse_average_linkage(*args)
    monkeypatch.delenv("DREP_TPU_NO_NATIVE")
    return out


@pytest.mark.parametrize("path", ["native", "python"])
def test_an_unobserved_pair_inside_an_accepted_merge_books_one_on_either_path(path, monkeypatch):
    """Four genomes: 0-1 and 2-3 tight, 0-2, 0-3 and 1-2 observed at 0.04, the
    pair 1-3 beyond the retention bound and so unobserved: {0, 1} and {2, 3}
    merge at (3 x 0.04 + 0.25) / 4 = 0.0925 under the cutoff, on the bound
    alone. Full-matrix UPGMA, with the pair at its true 0.6, would not."""
    import drep_tpu.native as native_mod
    from drep_tpu.ops.linkage import sparse_average_linkage, sparse_linkage_account

    if path == "native" and native_mod.get_library() is None:
        pytest.skip("no compiler: native path unavailable")
    ii = np.array([0, 2, 0, 0, 1])
    jj = np.array([1, 3, 2, 3, 2])
    dd = np.array([0.01, 0.01, 0.04, 0.04, 0.04])
    args = (4, ii, jj, dd, 0.10, 0.25)
    labels, approx = sparse_average_linkage(*args) if path == "native" else _python_path(monkeypatch, *args)
    assert approx == 1 and len(set(labels.tolist())) == 1
    did = sparse_linkage_account(4, ii, jj, dd, labels, 0.10, approx)
    assert did["uncertified_merges"] == 1 and did["merges"] == 3
    # one component of the cutoff graph, five of its six pairs under the cutoff: loose
    assert (did["components"], did["cliques"], did["loose_components"], did["rows_loose"]) == (1, 0, 1, 4)
    # the pair observed, at its true distance: no merge is uncertified, and the clusters stay two
    full = (4, np.append(ii, 1), np.append(jj, 3), np.append(dd, 0.6), 0.10, 1.0)
    labels, approx = sparse_average_linkage(*full) if path == "native" else _python_path(monkeypatch, *full)
    assert approx == 0 and len(set(labels.tolist())) == 2


def test_the_account_tells_cliques_from_loose_components_by_hand():
    from drep_tpu.ops.linkage import sparse_linkage_account

    # a triangle (clique), a path of three (loose), a pair (clique), two singletons; one retained
    # pair over the cutoff between the triangle and the path
    ii = np.array([0, 0, 1, 3, 4, 6, 2])
    jj = np.array([1, 2, 2, 4, 5, 7, 3])
    dd = np.array([.05, .05, .05, .08, .08, .02, .2])
    labels = np.array([1, 1, 1, 2, 2, 3, 4, 4, 5, 6])
    did = sparse_linkage_account(10, ii, jj, dd, labels, 0.10, 0)
    assert did == {"genomes": 10, "components": 5, "singletons": 2, "cliques": 2, "loose_components": 1,
                   "rows_loose": 3, "largest": 3, "edges_retained": 7, "edges_under_cutoff": 6,
                   "edges_between_clusters": 2, "merges": 4, "uncertified_merges": 0}
    assert sparse_linkage_account(0, ii[:0], jj[:0], dd[:0], labels[:0], 0.1, 0)["components"] == 0
    lone = sparse_linkage_account(3, ii[:0], jj[:0], dd[:0], np.arange(3), 0.1, 0)
    assert (lone["components"], lone["singletons"], lone["largest"], lone["merges"]) == (3, 3, 1, 0)


@pytest.mark.parametrize("alg", ["average", "single"])
def test_every_streaming_job_books_the_linkage_with_no_loose_component_on_cliques(alg, tmp_path):
    """The five streaming cells that were there plant cliques: `loose_components` 0."""
    from benchmark import cells
    from drep_tpu import controller

    gen = cells.load_module(os.path.join(REPO, "benchmark", "generators", "planted_sketches.py"))
    cfg = cells.read_json(os.path.join(REPO, "benchmark", "configs", "mags_5k.json"))
    cfg["data"].update({"n": 72, "s_bottom": 64, "own_bottom": 10, "s_scaled": 200})
    wd = gen.prepare(cfg, 54, str(tmp_path))["workdir"]
    controller.main(["compare", wd, "--skip_plots", "--SkipSecondary", "-ms", "64", "--streaming_primary",
                     "--streaming_block", "32", "--clusterAlg", alg])
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        did = json.load(f)["primary_linkage"]
    assert set(did) == KEYS | {"tree"} and did["genomes"] == 72
    assert did["loose_components"] == did["rows_loose"] == did["uncertified_merges"] == 0
    assert did["edges_between_clusters"] == 0 and did["edges_retained"] == did["edges_under_cutoff"]
    assert did["components"] == did["singletons"] + did["cliques"] == 72 - did["merges"]

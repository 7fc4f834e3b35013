"""The index verbs' spans and record section (ISSUE 50): the `index/*` spans
partition an update's `job` span, `index build` opens the same spans where the
same functions run, the record's `index` section counts what the verb did, and
with events off the spans leave no file and never reach the sink."""

from __future__ import annotations

import json
import os

import pytest

from tests._index_testlib import write_genome_set

UPDATE_SPANS = {"index/load", "index/sketch", "index/admit", "index/rect_compare", "index/partition", "index/secondary",
                "index/score", "index/publish"}
PUBLISH_SPANS = {"index/publish_sketch", "index/publish_edges", "index/publish_state",
                 "index/publish_manifest"}
INDEX_COUNTERS = {"n_old", "admitted", "generation", "pairs_compared", "tiles", "new_edges",
                  "components_reclustered", "clusters_reused", "clusters_recomputed", "members_recomputed",
                  "secondary_calls", "singletons_scored", "score_calls", "bytes_loaded", "bytes_published",
                  "files_published", "parts_written"}


@pytest.fixture(scope="module")
def verbs(tmp_path_factory):
    """`index build -g` of two clusters of two, then `index update` of a batch
    that joins one, founds one and adds a singleton, through the CLI's own
    function with events at their default (off): each verb's record."""
    from drep_tpu import controller
    from drep_tpu.utils import telemetry

    tmp = tmp_path_factory.mktemp("index_spans")
    paths = write_genome_set(str(tmp / "genomes"), [3, 2, 2, 1], seed=5)
    first, batch = [paths[i] for i in (0, 1, 3, 4)], [paths[i] for i in (2, 5, 6, 7)]
    idx = str(tmp / "idx")
    emitted: list[str] = []
    emit = telemetry._emit
    telemetry._emit = lambda ev, ph, args: (emitted.append(ev), emit(ev, ph, args))
    try:
        records = {}
        for verb, argv in (("build", ["index", "build", idx, "-g", *first, "--length", "0"]),
                           ("update", ["index", "update", idx, "-g", *batch])):
            controller.main(argv)
            with open(os.path.join(idx, "log", "perf_counters.json")) as f:
                records[verb] = json.load(f)
    finally:
        telemetry._emit = emit
    return {"records": records, "idx": idx, "emitted": emitted}


def test_the_index_spans_partition_the_updates_job(verbs):
    ph = verbs["records"]["update"]["phases"]
    inside = UPDATE_SPANS | PUBLISH_SPANS
    assert inside <= set(ph) and all(ph[n]["thread"] == "main" for n in inside | {"job"})
    # the main thread's self seconds add up to the job: every second has one name
    mains = sum(p["self_seconds"] for p in ph.values() if p["thread"] == "main")
    assert mains == pytest.approx(ph["job"]["seconds"], rel=0.01)
    assert all(ph[n]["seconds"] <= ph["job"]["seconds"] + 1e-3 for n in inside)
    # what the named spans leave of the job is a sliver of it (test_fasta_reference's tolerance)
    assert ph["job"]["self_seconds"] <= 0.2 * ph["job"]["seconds"] + 0.02, ph["job"]
    assert ph["index/publish"]["self_seconds"] <= 0.2 * ph["index/publish"]["seconds"] + 0.02
    assert sum(ph[n]["seconds"] for n in PUBLISH_SPANS) <= ph["index/publish"]["seconds"] + 1e-3
    # the shared code books its own spans inside the index's
    assert ph["primary/pack"]["seconds"] <= ph["index/rect_compare"]["seconds"] + 1e-3
    assert ph["stage:index_rect_compare"]["calls"] == 1
    # a joined cluster, a founded one, a new singleton: one secondary span a cluster of two or
    # more, and ONE score span over the three (ISSUE 51: it was one a cluster)
    assert ph["index/secondary"]["calls"] == 2 and ph["index/score"]["calls"] == 1


@pytest.mark.parametrize("verb", ["build", "update"])
def test_the_rectangles_pack_and_its_edges_sort_have_names_of_their_own(verbs, verb):
    """ISSUE 52: the union's `pack_sketches` was self time of
    `index/rect_compare`, the edges' lexsort of the verb's `job`."""
    ph = verbs["records"][verb]["phases"]
    rect, pack, order = ph["index/rect_compare"], ph["index/rect_pack"], ph["index/rect_sort"]
    assert pack["calls"] == order["calls"] == rect["calls"] == 1
    assert pack["thread"] == order["thread"] == "main"
    # the pack lies inside the rectangle's span: what the span keeps for itself excludes it
    assert rect["self_seconds"] <= rect["seconds"] - pack["seconds"] + 1e-3
    assert "cpu_s" in pack and "self_minor_faults" in order  # every entry carries the host's fields


def test_index_build_opens_the_same_spans_where_the_same_functions_run(verbs):
    ph = verbs["records"]["build"]["phases"]
    assert (UPDATE_SPANS - {"index/load"}) | PUBLISH_SPANS <= set(ph) and "index/load" not in ph
    did = verbs["records"]["build"]["index"]
    assert did["generation"] == 0 and did["admitted"] == 4 and did["n_old"] == 0
    assert did["clusters_recomputed"] == 2 and did["clusters_reused"] == 0 and did["tiles"] == 1
    assert did["score_calls"] == 1 == ph["index/score"]["calls"]


def test_the_records_index_section_counts_what_the_update_did(verbs):
    did = verbs["records"]["update"]["index"]
    assert set(did) == INDEX_COUNTERS
    assert (did["n_old"], did["admitted"], did["generation"]) == (4, 4, 1)
    assert did["pairs_compared"] == verbs["records"]["update"]["stages"]["index_rect_compare"]["pairs"] > 0
    # g02 joins the first cluster, g05 and g06 found one, g07 is alone: three changed, one reused
    assert (did["clusters_recomputed"], did["clusters_reused"], did["components_reclustered"]) == (3, 1, 3)
    assert (did["members_recomputed"], did["secondary_calls"], did["singletons_scored"]) == (6, 2, 1)
    # the three changed clusters went through one score_and_pick call
    assert did["score_calls"] == 1
    assert did["new_edges"] >= 2 and did["tiles"] == 1
    # sketch shard, edge shard, state: three files and no part at this size
    assert (did["files_published"], did["parts_written"]) == (3, 0)
    on_disk = sum(os.path.getsize(os.path.join(verbs["idx"], sub, f))
                  for sub in ("sketches", "edges", "state")
                  for f in os.listdir(os.path.join(verbs["idx"], sub)) if "_g000001" in f)
    assert did["bytes_published"] == on_disk
    assert did["bytes_loaded"] > 0


def test_the_spans_cost_nothing_with_events_off(verbs):
    assert verbs["emitted"] == [], "events off reached the sink"
    log = os.path.join(verbs["idx"], "log")
    assert not [f for f in os.listdir(log) if f.startswith("events.")]

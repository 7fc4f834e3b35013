"""The dense primary's tree is built only where the dendrogram will read it
(ISSUE 37): `Cdb` comes from the components of the cutoff graph either way,
`clustering.pickle` holds the whole tree only in a job that plots, and a later
plotting run builds it from the distances the work directory keeps."""

import glob
import json
import os
import pickle

import pytest

from drep_tpu.utils import telemetry
from drep_tpu.workflows import compare_wrapper

DENDROGRAM = os.path.join("figures", "Primary_clustering_dendrogram.pdf")


def _clustering(wd: str) -> dict:
    with open(os.path.join(wd, "data", "Clustering_files", "clustering.pickle"), "rb") as f:
        return pickle.load(f)


def _record(wd: str) -> dict:
    with open(os.path.join(wd, "log", "perf_counters.json")) as f:
        return json.load(f)


def _cdb_bytes(wd: str) -> bytes:
    with open(os.path.join(wd, "data_tables", "Cdb.csv"), "rb") as f:
        return f.read()


@pytest.fixture(scope="module")
def plotted(tmp_path_factory, genome_paths):
    wd = str(tmp_path_factory.mktemp("tree_plotted") / "wd")
    compare_wrapper(wd, genome_paths)
    return {"wd": wd, "record": _record(wd)}


@pytest.fixture(scope="module")
def unplotted(tmp_path_factory, genome_paths):
    wd = str(tmp_path_factory.mktemp("tree_unplotted") / "wd")
    compare_wrapper(wd, genome_paths, skip_plots=True, events="on")
    telemetry.configure()
    return {"wd": wd, "record": _record(wd)}


def test_a_job_that_plots_builds_the_tree_and_draws_it(plotted, genome_paths):
    n = len(genome_paths)
    assert _clustering(plotted["wd"])["primary_linkage"].shape == (n - 1, 4)
    assert os.path.getsize(os.path.join(plotted["wd"], DENDROGRAM)) > 2000
    assert plotted["record"]["primary_linkage"]["tree"] == "built"


def test_a_job_under_skip_plots_stores_the_empty_tree_and_the_same_cdb(plotted, unplotted):
    cf = _clustering(unplotted["wd"])
    assert cf["primary_linkage"].shape == (0, 4)
    assert cf["primary_dist"] is not None
    assert not os.path.exists(os.path.join(unplotted["wd"], DENDROGRAM))
    assert _cdb_bytes(unplotted["wd"]) == _cdb_bytes(plotted["wd"])


@pytest.mark.parametrize("job", ["plotted", "unplotted"])
def test_the_record_accounts_for_every_component(job, request, genome_paths):
    did = request.getfixturevalue(job)["record"]["primary_linkage"]
    assert set(did) == {"genomes", "components", "singletons", "cliques", "linkage_calls",
                        "rows_linked", "largest", "tree"}
    assert did["genomes"] == len(genome_paths)
    assert did["singletons"] + did["cliques"] + did["linkage_calls"] == did["components"]
    # the fixture: {A, B, C} and {D, E}, every pair inside under the cutoff
    assert (did["components"], did["cliques"], did["linkage_calls"], did["largest"]) == (2, 2, 0, 3)
    assert did["tree"] == ("built" if job == "plotted" else "skipped")


def test_the_span_says_what_it_did_on_its_closing_line(unplotted):
    lines = []
    for path in glob.glob(os.path.join(unplotted["wd"], "log", "events.p*.jsonl")):
        with open(path) as f:
            lines += [json.loads(ln) for ln in f]
    spans = {ev["ph"]: ev["args"] for ev in lines if ev["ev"] == "primary/linkage"}
    assert spans["B"] == {"genomes": 5, "tree": "skipped"}
    closing = spans["E"]
    assert (closing["genomes"], closing["tree"]) == (5, "skipped")
    assert (closing["components"], closing["linkage_calls"], closing["largest"]) == (2, 0, 3)


def test_a_later_plotting_run_draws_the_tree_from_the_kept_distances(unplotted, genome_paths):
    """Last of the module's tests on `unplotted`: it resumes the directory."""
    wd = unplotted["wd"]
    compare_wrapper(wd, genome_paths)  # Cdb present: nothing is clustered again
    assert os.path.getsize(os.path.join(wd, DENDROGRAM)) > 2000
    assert _clustering(wd)["primary_linkage"].shape == (0, 4)  # the pickle is the first job's


def test_without_kept_distances_a_later_plotting_run_says_why(tmp_path, genome_paths):
    """Beyond `mdb_dense_limit` the work directory keeps no square matrix: the
    later run draws the other figures and names the flag, once."""
    wd = str(tmp_path / "wd")
    compare_wrapper(wd, genome_paths, skip_plots=True, mdb_dense_limit=2)
    assert _clustering(wd)["primary_dist"] is None
    compare_wrapper(wd, genome_paths, mdb_dense_limit=2)
    assert not os.path.exists(os.path.join(wd, DENDROGRAM))
    assert os.path.exists(os.path.join(wd, "figures", "Secondary_clustering_dendrograms.pdf"))
    with open(os.path.join(wd, "log", "logger.log")) as f:
        said = [ln for ln in f if "no primary dendrogram" in ln]
    assert len(said) == 1 and "--skip_plots" in said[0]

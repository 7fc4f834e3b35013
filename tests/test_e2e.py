"""End-to-end pipeline tests on the bundled 5-genome fixture.

Mirrors the reference's functional-test pattern (run the CLI on
tests/genomes, assert on the resulting data tables — SURVEY.md §4), but
against the TPU-native engines with no external binaries.

Fixture construction (tests/genomes/generate.py) pins the expected answer:
primary clusters {A,B,C} and {D,E}; secondary {A,B}, {C}, {D,E}.
"""

import os

import pandas as pd
import pytest

from drep_tpu.workflows import compare_wrapper, dereplicate_wrapper


def _clusters_of(cdb: pd.DataFrame) -> dict[str, str]:
    return dict(zip(cdb["genome"], cdb["secondary_cluster"]))


@pytest.fixture(scope="module")
def compare_wd(tmp_path_factory, genome_paths):
    wd = str(tmp_path_factory.mktemp("compare_wd"))
    cdb = compare_wrapper(wd, genome_paths, skip_plots=True)
    return wd, cdb


def test_compare_expected_clusters(compare_wd):
    _, cdb = compare_wd
    by_genome = cdb.set_index("genome")
    prim = by_genome["primary_cluster"]
    assert prim["genome_A.fasta"] == prim["genome_B.fasta"] == prim["genome_C.fasta"]
    assert prim["genome_D.fasta"] == prim["genome_E.fasta"]
    assert prim["genome_A.fasta"] != prim["genome_D.fasta"]

    sec = by_genome["secondary_cluster"]
    assert sec["genome_A.fasta"] == sec["genome_B.fasta"]
    assert sec["genome_C.fasta"] != sec["genome_A.fasta"]
    assert sec["genome_D.fasta"] == sec["genome_E.fasta"]
    assert cdb["secondary_cluster"].nunique() == 3


def test_compare_tables_stored(compare_wd):
    wd, _ = compare_wd
    for table in ("Bdb", "Mdb", "Ndb", "Cdb", "Gdb", "genomeInformation"):
        assert os.path.exists(os.path.join(wd, "data_tables", f"{table}.csv")), table


def test_mdb_schema_and_sanity(compare_wd):
    wd, _ = compare_wd
    mdb = pd.read_csv(os.path.join(wd, "data_tables", "Mdb.csv"))
    assert set(["genome1", "genome2", "dist", "similarity"]) <= set(mdb.columns)
    assert len(mdb) == 25  # dense 5x5 ordered pairs
    ab = mdb[(mdb.genome1 == "genome_A.fasta") & (mdb.genome2 == "genome_B.fasta")]["dist"].iloc[0]
    ad = mdb[(mdb.genome1 == "genome_A.fasta") & (mdb.genome2 == "genome_D.fasta")]["dist"].iloc[0]
    assert ab < 0.02  # ~1% mutated
    assert ad > 0.3  # unrelated


def test_ndb_ani_close_to_mutation_rate(compare_wd):
    wd, _ = compare_wd
    ndb = pd.read_csv(os.path.join(wd, "data_tables", "Ndb.csv"))
    ab = ndb[(ndb.querry == "genome_A.fasta") & (ndb.reference == "genome_B.fasta")]["ani"].iloc[0]
    assert 0.985 < ab < 0.995  # 1% point mutations -> ANI ~0.99
    de = ndb[(ndb.querry == "genome_D.fasta") & (ndb.reference == "genome_E.fasta")]["ani"].iloc[0]
    assert 0.993 < de < 0.999  # 0.5% -> ~0.995


def test_resume_skips_recompute(compare_wd, genome_paths, monkeypatch):
    wd, cdb1 = compare_wd
    # poison the sketching path: resume must not re-sketch
    import drep_tpu.cluster.controller as cc

    def boom(*a, **k):
        raise AssertionError("resume should not re-run sketching")

    monkeypatch.setattr(cc, "sketch_genomes", boom)
    cdb2 = compare_wrapper(wd, genome_paths, skip_plots=True)
    pd.testing.assert_frame_equal(
        cdb1.reset_index(drop=True), cdb2.reset_index(drop=True), check_dtype=False
    )


def test_cli_subprocess_compare(tmp_path, genome_paths):
    """The full parse_args -> Controller -> workflow path through a real
    subprocess (`python -m drep_tpu compare ...`) — the reference's
    functional-test shape (SURVEY.md §4), which the in-process tests skip."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    wd = str(tmp_path / "wd")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-m", "drep_tpu", "compare", wd, "-g", *genome_paths, "--skip_plots"],
        capture_output=True, text=True, cwd=repo, timeout=300, env=env,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    cdb = pd.read_csv(os.path.join(wd, "data_tables", "Cdb.csv"))
    assert cdb["secondary_cluster"].nunique() == 3
    assert "compare finished" in res.stderr


def test_resume_warns_on_estimator_boundary(tmp_path, genome_paths):
    """A resumed workdir whose primary took another route than this run
    would (N, the device count or a flag crossed a route boundary: a ring's
    tables are not a streaming run's byte for byte) must still resume —
    but with a loud warning that names both routes."""
    import json

    wd = str(tmp_path / "wd")
    compare_wrapper(wd, genome_paths, skip_plots=True)
    loc = os.path.join(wd, "log", "cluster_arguments.json")
    with open(loc) as f:
        args = json.load(f)
    assert "primary_estimator_resolved" in args
    was = args["primary_estimator_resolved"]
    args["primary_estimator_resolved"] = "ring_sort" if was != "ring_sort" else "sort"
    # snapshots carry an in-band checksum (utils/durableio.py); a hand
    # edit must drop the now-stale crc — a crc-less snapshot is
    # legacy-accepted, a mismatched one is (correctly) treated as rot
    args.pop("crc", None)
    with open(loc, "w") as f:
        json.dump(args, f)
    cdb = compare_wrapper(wd, genome_paths, skip_plots=True)
    # the framework logger does not propagate (its own handlers own the
    # stream) — assert via the workdir log file the file handler writes
    with open(os.path.join(wd, "log", "logger.log")) as f:
        log = f.read()
    assert f"primary took the route {args['primary_estimator_resolved']!r}" in log
    assert f"would take {was!r}" in log
    assert "skipping recompute" in log  # resumed, not recomputed
    assert len(cdb) == len(genome_paths)


def test_dereplicate_winners(tmp_path, genome_paths):
    wd = str(tmp_path / "derep_wd")
    quality = pd.DataFrame(
        {
            "genome": [os.path.basename(p) for p in genome_paths],
            "completeness": [99.0, 90.0, 85.0, 95.0, 94.0],
            "contamination": [0.5, 1.0, 2.0, 0.1, 0.2],
        }
    )
    qcsv = str(tmp_path / "quality.csv")
    quality.to_csv(qcsv, index=False)
    wdb = dereplicate_wrapper(wd, genome_paths, genomeInfo=qcsv, skip_plots=True, length=50_000)
    assert len(wdb) == 3  # one winner per secondary cluster
    winners = set(wdb["genome"])
    assert "genome_A.fasta" in winners  # best quality in {A,B}
    assert "genome_C.fasta" in winners  # singleton
    assert "genome_D.fasta" in winners  # best quality in {D,E}
    out_dir = os.path.join(wd, "dereplicated_genomes")
    assert sorted(os.listdir(out_dir)) == sorted(winners)
    # full dereplicate table set present
    for table in ("Sdb", "Wdb", "Cdb"):
        assert os.path.exists(os.path.join(wd, "data_tables", f"{table}.csv"))
    sdb = pd.read_csv(os.path.join(wd, "data_tables", "Sdb.csv"))
    assert sdb["quality_informed"].all()  # genomeInfo was provided


def test_dereplicate_length_filter(tmp_path, genome_paths):
    wd = str(tmp_path / "filter_wd")
    wdb = dereplicate_wrapper(
        wd, genome_paths, skip_plots=True, length=115_000, ignoreGenomeQuality=True
    )
    bdb = pd.read_csv(os.path.join(wd, "data_tables", "Bdb.csv"))
    # only A/B/C are >= 115kb
    assert set(bdb["genome"]) == {"genome_A.fasta", "genome_B.fasta", "genome_C.fasta"}
    # no quality info was available: the Sdb must say its scores are
    # quality-blind (the reference would have aborted outright)
    sdb = pd.read_csv(os.path.join(wd, "data_tables", "Sdb.csv"))
    assert not sdb["quality_informed"].any()


def test_evaluate_warnings_file(compare_wd):
    wd, _ = compare_wd
    assert os.path.exists(os.path.join(wd, "log", "warnings.txt"))


def test_skip_secondary(tmp_path, genome_paths):
    wd = str(tmp_path / "skipsec_wd")
    cdb = compare_wrapper(wd, genome_paths, skip_plots=True, SkipSecondary=True)
    assert all(c.endswith("_0") for c in cdb["secondary_cluster"])
    assert cdb["secondary_cluster"].nunique() == 2


def test_cli_parse_and_check_dependencies(capsys):
    from drep_tpu.argparser import parse_args
    from drep_tpu.controller import Controller

    args = parse_args(["compare", "/tmp/x", "-g", "a.fa", "--S_ani", "0.97"])
    assert args.S_ani == 0.97
    assert args.primary_algorithm == "jax_mash"
    Controller().check_dependencies_operation()  # must not raise

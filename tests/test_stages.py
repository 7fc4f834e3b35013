"""Stage-level tests: filter quality paths, greedy + multiround clustering.

These exercise the reference's flag surface (SURVEY.md §2:
--greedy_secondary_clustering, --multiround_primary_clustering,
--genomeInfo) end-to-end on the 5-genome fixture, asserting the partitions
match the default all-pairs paths.
"""

import pandas as pd
import pytest

from drep_tpu.filter import d_filter_wrapper, load_genome_info
from drep_tpu.workdir import WorkDirectory
from drep_tpu.workflows import compare_wrapper, dereplicate_wrapper


def _quality_df(genomes, strain_het=None):
    df = pd.DataFrame(
        {
            "genome": genomes,
            "completeness": [99.0, 90.0, 85.0, 95.0, 94.0][: len(genomes)],
            "contamination": [0.5, 1.0, 2.0, 0.1, 0.2][: len(genomes)],
        }
    )
    if strain_het is not None:
        df["strain_heterogeneity"] = strain_het
    return df


def _partition(cdb: pd.DataFrame) -> dict:
    """genome -> frozenset of co-members (label-independent partition)."""
    groups = cdb.groupby("secondary_cluster")["genome"].apply(frozenset)
    return {g: grp for grp in groups for g in grp}


# ---- filter ----------------------------------------------------------------


def test_filter_quality_drops_low_completeness(tmp_path, bdb):
    wd = WorkDirectory(str(tmp_path / "wd"))
    quality = _quality_df(list(bdb["genome"]))
    quality.loc[quality["genome"] == "genome_C.fasta", "completeness"] = 10.0
    filtered, sketches = d_filter_wrapper(wd, bdb, genomeInfo=quality)
    # the genome the table drops was read for its stats alone, every other sketched
    assert "bottom" not in sketches.results["genome_C.fasta"]
    assert all("bottom" in r for g, r in sketches.results.items() if g != "genome_C.fasta")
    assert "genome_C.fasta" not in set(filtered["genome"])
    assert len(filtered) == len(bdb) - 1


def test_filter_missing_genome_in_quality_raises(tmp_path, bdb):
    wd = WorkDirectory(str(tmp_path / "wd"))
    quality = _quality_df(list(bdb["genome"])[:-1])  # one genome missing
    with pytest.raises(ValueError, match="missing from genomeInfo"):
        d_filter_wrapper(wd, bdb, genomeInfo=quality)


def test_load_genome_info_checkm_column_names(tmp_path):
    path = str(tmp_path / "q.csv")
    pd.DataFrame(
        {
            "Bin Id": ["a"],
            "Completeness": [99.0],
            "Contamination": [1.0],
            "Strain heterogeneity": [12.5],
        }
    ).to_csv(path, index=False)
    df = load_genome_info(path)
    assert list(df.columns) == [
        "genome", "completeness", "contamination", "strain_heterogeneity",
    ]


def test_strain_heterogeneity_feeds_score(tmp_path, genome_paths):
    """With a big strW-relevant difference, the strain_heterogeneity column
    must flip the winner within the {A, B} cluster."""
    names = [p.split("/")[-1] for p in genome_paths]
    # B gets a huge strain-het bonus; otherwise A wins on completeness
    strain = [0.0 if n != "genome_B.fasta" else 1000.0 for n in names]
    q = _quality_df(names, strain_het=strain)
    qpath = str(tmp_path / "q.csv")
    q.to_csv(qpath, index=False)
    wdb = dereplicate_wrapper(
        str(tmp_path / "wd"), genome_paths, genomeInfo=qpath, skip_plots=True
    )
    assert "genome_B.fasta" in set(wdb["genome"])
    assert "genome_A.fasta" not in set(wdb["genome"])


# ---- greedy secondary ------------------------------------------------------


def test_greedy_matches_default_partition(tmp_path, genome_paths):
    cdb_default = compare_wrapper(
        str(tmp_path / "wd1"), genome_paths, skip_plots=True
    )
    cdb_greedy = compare_wrapper(
        str(tmp_path / "wd2"),
        genome_paths,
        greedy_secondary_clustering=True,
        skip_plots=True,
    )
    assert _partition(cdb_default) == _partition(cdb_greedy)


# ---- multiround primary ----------------------------------------------------


def test_multiround_matches_default_primary(tmp_path, genome_paths):
    cdb_default = compare_wrapper(
        str(tmp_path / "wd1"), genome_paths, skip_plots=True
    )
    cdb_multi = compare_wrapper(
        str(tmp_path / "wd2"),
        genome_paths,
        multiround_primary_clustering=True,
        primary_chunksize=2,
        skip_plots=True,
    )
    prim_default = cdb_default.groupby("primary_cluster")["genome"].apply(frozenset)
    prim_multi = cdb_multi.groupby("primary_cluster")["genome"].apply(frozenset)
    assert set(prim_default) == set(prim_multi)
    assert _partition(cdb_default) == _partition(cdb_multi)


# ---- murmur3 hash option ----------------------------------------------------


def test_murmur3_hash_matches_default_partition(tmp_path, genome_paths):
    """--hash murmur3 (Mash-compatible hashing) changes sketch VALUES but
    must not change the fixture's clustering — both hashes sample the same
    k-mer sets uniformly."""
    cdb_default = compare_wrapper(
        str(tmp_path / "wd1"), genome_paths, skip_plots=True
    )
    cdb_m3 = compare_wrapper(
        str(tmp_path / "wd2"), genome_paths, hash="murmur3", skip_plots=True
    )
    assert _partition(cdb_default) == _partition(cdb_m3)


# ---- evaluate: Widb ---------------------------------------------------------


def test_widb_written_on_dereplicate(tmp_path, genome_paths):
    names = [p.split("/")[-1] for p in genome_paths]
    q = _quality_df(names)
    qpath = str(tmp_path / "q.csv")
    q.to_csv(qpath, index=False)
    wdb = dereplicate_wrapper(
        str(tmp_path / "wd"), genome_paths, genomeInfo=qpath, skip_plots=True
    )
    widb = pd.read_csv(tmp_path / "wd" / "data_tables" / "Widb.csv")
    assert set(widb["genome"]) == set(wdb["genome"])
    for col in ("secondary_cluster", "length", "N50", "completeness", "contamination", "score"):
        assert col in widb.columns, col

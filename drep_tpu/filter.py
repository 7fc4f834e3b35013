"""Filter stage: drop genomes by length and quality before clustering.

Reference parity: drep/d_filter.py (SURVEY.md §2; reference mount empty) —
defaults --length 50000, --completeness 75, --contamination 25. Quality
comes from a user-supplied genomeInfo CSV (genome, completeness,
contamination) or, when available on $PATH, from CheckM via subprocess
(run_checkm_wrapper); without either, only the length filter applies and a
`!!!` warning is emitted (the reference aborts dereplicate without quality —
we soften this to keep the TPU pipeline runnable in binary-free
environments, with the same loud warning).

The filter reads no FASTA itself: every file is opened and parsed once, by
the ingest pool, between the stage's two halves. First the quality table,
which needs no FASTA, says which genomes completeness or contamination will
drop. Then one pass of the pool over the whole input Bdb
(`cluster.controller.read_for_filter`, a `stage:ingest_or_cache` of its own,
outside `stage:filter`) returns every genome's length, N50 and contigs, with
the sketches of every genome not known to be dropped; a genome the table
drops is read for its stats alone. Then the rules are applied to the pool's
numbers and the tables written; `stage:cluster` keeps the sketches of the
genomes that passed and sketches nothing. What the pool sketched for nothing
is the genomes that only their length (or CheckM) drops.

A rerun on a work directory whose `genomeInformation` covers the genomes,
under the same `filter` arguments, takes its stats from that table and reads
no FASTA here.
"""

from __future__ import annotations

import os
import shutil
import subprocess
from typing import TYPE_CHECKING, Any

import pandas as pd

from drep_tpu.utils.logger import get_logger, user_warning
from drep_tpu.utils.profiling import counters
from drep_tpu.workdir import WorkDirectory
from drep_tpu.errors import UserInputError

if TYPE_CHECKING:
    from drep_tpu.ingest import IngestPass

FILTER_DEFAULTS: dict[str, Any] = {
    "length": 50_000,
    "completeness": 75.0,
    "contamination": 25.0,
    "ignoreGenomeQuality": False,
    "checkM_method": "lineage_wf",  # reference --checkM_method (or taxonomy_wf)
}


def load_genome_info(source) -> pd.DataFrame:
    """genomeInfo from a CSV path or DataFrame; validates required columns."""
    df = pd.read_csv(source) if isinstance(source, str) else source.copy()
    # tolerate dRep's checkm-style column names
    renames = {
        "Completeness": "completeness",
        "Contamination": "contamination",
        "Bin Id": "genome",
        "Strain heterogeneity": "strain_heterogeneity",
    }
    return df.rename(columns={k: v for k, v in renames.items() if k in df.columns})


def run_checkm_wrapper(
    bdb: pd.DataFrame,
    out_dir: str,
    processes: int = 1,
    checkm_method: str = "lineage_wf",
) -> pd.DataFrame:
    """CheckM completeness/contamination via subprocess (reference L0 path).

    Reference parity: d_filter.py::run_checkM_wrapper, including the
    --checkM_method choice (lineage_wf default; taxonomy_wf runs the
    domain-level workflow `checkm taxonomy_wf domain Bacteria`). Only used
    when `checkm` exists on $PATH; otherwise callers should pass
    --genomeInfo.
    """
    if shutil.which("checkm") is None:
        raise UserInputError("checkm not found on $PATH — supply --genomeInfo instead")
    if checkm_method not in ("lineage_wf", "taxonomy_wf"):
        raise UserInputError(f"unknown checkM_method {checkm_method!r}")
    genome_dir = os.path.join(out_dir, "checkm_genomes")
    os.makedirs(genome_dir, exist_ok=True)
    # checkm selects bins by extension (-x) and reports Bin Id without the
    # extension — copy under a normalized unique stem + .fa and map back
    stem_to_genome: dict[str, str] = {}
    for i, row in enumerate(bdb.itertuples()):
        stem = f"bin_{i}"
        stem_to_genome[stem] = row.genome
        dst = os.path.join(genome_dir, f"{stem}.fa")
        if not os.path.exists(dst):
            shutil.copy(row.location, dst)
    res_dir = os.path.join(out_dir, "checkm_out")
    tab = os.path.join(out_dir, "checkm.tsv")
    method_args = (
        ["lineage_wf", genome_dir, res_dir]
        if checkm_method == "lineage_wf"
        # the reference's taxonomy_wf path pins the domain-level marker set
        else ["taxonomy_wf", "domain", "Bacteria", genome_dir, res_dir]
    )
    cmd = [
        "checkm", *method_args,
        "-x", "fa", "-t", str(processes), "--tab_table", "-f", tab,
    ]
    res = subprocess.run(cmd, capture_output=True, text=True)
    if res.returncode != 0:
        raise RuntimeError(f"checkm failed: {res.stderr[-2000:]}")
    chdb = pd.read_csv(tab, sep="\t")
    chdb = chdb.rename(
        columns={
            "Bin Id": "genome",
            "Completeness": "completeness",
            "Contamination": "contamination",
            "Strain heterogeneity": "strain_heterogeneity",
        }
    )
    chdb["genome"] = chdb["genome"].map(stem_to_genome)
    if chdb["genome"].isna().any():
        raise RuntimeError("checkm output contained unknown bin ids")
    cols = ["genome", "completeness", "contamination"]
    if "strain_heterogeneity" in chdb.columns:  # feeds the strW scoring term
        cols.append("strain_heterogeneity")
    return chdb[cols]


def d_filter_wrapper(
    wd: WorkDirectory,
    bdb: pd.DataFrame,
    genomeInfo=None,
    **kwargs,
) -> tuple[pd.DataFrame, IngestPass | None]:
    """Filter Bdb; stores the genomeInformation, Bdb and genomeInfo tables.
    Returns (the filtered Bdb, the pool's pass over the FASTAs for
    `d_cluster_wrapper` to keep its sketches from, or None where the stats
    were the work directory's and nothing was read)."""
    from drep_tpu.cluster.controller import read_for_filter

    kw = dict(FILTER_DEFAULTS)
    kw.update({k: v for k, v in kwargs.items() if v is not None})
    processes = kwargs.get("processes", 1)

    with counters.span("stage:filter"):
        with counters.span("filter/quality"):
            quality = _quality_table(bdb, genomeInfo)
        stats = _stored_stats(wd, bdb, kw)
        if stats is not None:
            get_logger().info("filter: genome stats from the work directory, no FASTA read")
            return _apply_rules(wd, bdb, stats, quality, kw, processes), None
        # no table in hand (CheckM, or no quality at all): every genome is a candidate
        dropped = set() if quality is None else set(
            bdb["genome"][~_quality_keep(bdb["genome"], quality, kw)[0]])
    sketches = read_for_filter(wd, bdb, dropped, **kwargs)
    with counters.span("stage:filter"):
        return _apply_rules(wd, bdb, sketches.stats, quality, kw, processes), sketches


def _stored_stats(wd: WorkDirectory, bdb: pd.DataFrame, kw) -> pd.DataFrame | None:
    """The stats of `bdb`'s genomes from a finished filter's
    `genomeInformation` (the `filter` arguments are stored last, and match),
    or None."""
    if not (wd.hasDb("genomeInformation")
            and wd.arguments_match("filter", {k: kw[k] for k in FILTER_DEFAULTS})):
        return None
    stored = wd.get_db("genomeInformation").set_index("genome")
    if not set(bdb["genome"]) <= set(stored.index):
        return None
    return stored.loc[list(bdb["genome"]), ["length", "N50", "contigs"]].reset_index()


def _quality_table(bdb: pd.DataFrame, genomeInfo) -> pd.DataFrame | None:
    """--genomeInfo, validated against `bdb`; None where none was given."""
    if genomeInfo is None:
        return None
    quality = load_genome_info(genomeInfo)
    missing = [c for c in ("genome", "completeness", "contamination") if c not in quality.columns]
    if missing:
        raise UserInputError(f"genomeInfo missing columns {missing}")
    return _covering(quality, bdb["genome"])


def _covering(quality: pd.DataFrame, genomes: pd.Series) -> pd.DataFrame:
    """`quality`, once it lists every one of `genomes`."""
    absent = genomes[~genomes.isin(quality["genome"])]
    if len(absent):
        raise UserInputError(f"genomes missing from genomeInfo: {list(absent)}")
    return quality


def _quality_keep(genomes: pd.Series, quality: pd.DataFrame, kw):
    """(passes both rules, fails completeness, fails contamination), a
    boolean Series each over `genomes`. A missing value passes neither rule."""
    q = quality.set_index("genome")
    low_comp = ~(genomes.map(q["completeness"]) >= kw["completeness"])
    high_cont = ~(genomes.map(q["contamination"]) <= kw["contamination"])
    return ~(low_comp | high_cont), low_comp, high_cont


def _apply_rules(wd, bdb, stats, quality, kw, processes) -> pd.DataFrame:
    """The length and quality rules over `stats` (genome, length, N50,
    contigs of every genome of `bdb`); `quality` is --genomeInfo's table,
    and where there is none CheckM's, if it is on $PATH and wanted. Stores
    the tables, the `filter` arguments and the `filter` counter; returns
    the filtered Bdb."""
    logger = get_logger()
    with counters.span("tables_io") as io:
        io.note(rows=len(stats), bytes=wd.store_db(stats, "genomeInformation"))

    keep = stats["length"] >= kw["length"]
    dropped_len = list(stats.loc[~keep, "genome"])
    if dropped_len:
        logger.info("filtered %d genomes below length %d: %s", len(dropped_len), kw["length"], dropped_len)

    dropped = (0, 0)
    with counters.span("filter/quality"):
        if quality is None and not kw["ignoreGenomeQuality"]:
            if shutil.which("checkm") is not None:
                quality = _covering(
                    run_checkm_wrapper(
                        bdb,
                        wd.get_dir(os.path.join("data", "checkM")),
                        processes,
                        checkm_method=kw["checkM_method"],
                    ),
                    stats["genome"],
                )
            else:
                user_warning(
                    "no --genomeInfo given and checkm not on $PATH — genome quality "
                    "filtering and quality-based scoring are DISABLED for this run"
                )
        if quality is not None:
            qkeep, low_comp, high_cont = _quality_keep(stats["genome"], quality, kw)
            dropped_q = list(stats.loc[keep & ~qkeep, "genome"])
            if dropped_q:
                logger.info("filtered %d genomes by quality: %s", len(dropped_q), dropped_q)
            wd.store_db(quality, "genomeInfo")
            dropped = (int((keep & low_comp).sum()), int((keep & high_cont).sum()))
            keep = keep & qkeep

    filtered = bdb[bdb["genome"].isin(stats.loc[keep, "genome"])].reset_index(drop=True)
    counters.add_filter(len(bdb), len(dropped_len), *dropped)
    if len(filtered) == 0:
        raise RuntimeError("all genomes were filtered out — relax --length/--completeness/--contamination")
    with counters.span("tables_io") as io:
        io.note(rows=len(filtered), bytes=wd.store_db(filtered, "Bdb"))
        wd.store_arguments("filter", {k: kw[k] for k in FILTER_DEFAULTS})
    logger.info("filter: %d/%d genomes pass", len(filtered), len(bdb))
    return filtered

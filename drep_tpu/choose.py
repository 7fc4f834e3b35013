"""Choose stage: score genomes, pick one winner per secondary cluster.

Reference parity: drep/d_choose.py (SURVEY.md §2; reference mount empty).
The scoring formula is the reference's (flag-weighted, defaults shown):

    score = comW(1)·completeness − conW(5)·contamination
          + strW(1)·strain_heterogeneity + N50W(0.5)·log10(N50)
          + sizeW(0)·log10(size) + centW(1)·(centrality − S_ani)

`centrality` is the genome's mean symmetrized ANI to the other members of
its secondary cluster (from Ndb). Winners are copied into
`<wd>/dereplicated_genomes/`. Without quality data the quality terms
contribute 0 (with a loud warning from the filter stage).
"""

from __future__ import annotations

import shutil
from typing import Any

import numpy as np
import pandas as pd

from drep_tpu import schemas
from drep_tpu.utils.logger import get_logger
from drep_tpu.utils.profiling import counters
from drep_tpu.workdir import WorkDirectory

SCORE_DEFAULTS: dict[str, Any] = {
    "completeness_weight": 1.0,   # -comW
    "contamination_weight": 5.0,  # -conW
    "strain_heterogeneity_weight": 1.0,  # -strW
    "N50_weight": 0.5,            # -N50W
    "size_weight": 0.0,           # -sizeW
    "centrality_weight": 1.0,     # -centW
    "S_ani": 0.95,
}


def compute_centrality(ndb: pd.DataFrame, cdb: pd.DataFrame) -> pd.Series:
    """Mean symmetrized ANI of each genome to co-members of its secondary
    cluster. Genomes with no comparisons (singletons) get centrality 0."""
    cent = pd.Series(0.0, index=cdb["genome"])
    if len(ndb) == 0:
        return cent
    cluster_of = cdb.set_index("genome")["secondary_cluster"]
    df = ndb.loc[ndb["querry"] != ndb["reference"], ["querry", "reference", "ani"]].copy()
    # canonical unordered pair, then mean over the (up to two) directions
    lo = np.minimum(df["querry"], df["reference"])
    hi = np.maximum(df["querry"], df["reference"])
    df["g1"], df["g2"] = lo, hi
    pair = df.groupby(["g1", "g2"], sort=False)["ani"].mean().reset_index()
    same = pair["g1"].map(cluster_of).to_numpy() == pair["g2"].map(cluster_of).to_numpy()
    pair = pair[same]
    if len(pair) == 0:
        return cent
    melted = pd.concat(
        [
            pair[["g1", "ani"]].rename(columns={"g1": "genome"}),
            pair[["g2", "ani"]].rename(columns={"g2": "genome"}),
        ]
    )
    per_genome = melted.groupby("genome")["ani"].mean()
    cent.update(per_genome)
    return cent


def score_genomes(
    cdb: pd.DataFrame,
    stats: pd.DataFrame,
    quality: pd.DataFrame | None,
    ndb: pd.DataFrame,
    extra_weights: pd.DataFrame | None = None,
    **kwargs,
) -> pd.DataFrame:
    kw = dict(SCORE_DEFAULTS)
    kw.update({k: v for k, v in kwargs.items() if v is not None and k in SCORE_DEFAULTS})

    df = cdb[["genome", "secondary_cluster"]].merge(
        stats[["genome", "length", "N50"]], on="genome", how="left"
    )
    if quality is not None:
        df = df.merge(quality, on="genome", how="left")
    for col in ("completeness", "contamination", "strain_heterogeneity"):
        if col not in df.columns:
            df[col] = 0.0
        df[col] = df[col].fillna(0.0)

    with counters.span("choose/centrality", pairs=len(ndb)):
        centrality = compute_centrality(ndb, cdb)
    df["centrality"] = df["genome"].map(centrality).fillna(0.0)

    score = (
        kw["completeness_weight"] * df["completeness"]
        - kw["contamination_weight"] * df["contamination"]
        + kw["strain_heterogeneity_weight"] * df["strain_heterogeneity"]
        + kw["N50_weight"] * np.log10(df["N50"].clip(lower=1))
        + kw["size_weight"] * np.log10(df["length"].clip(lower=1))
        + kw["centrality_weight"] * (df["centrality"] - kw["S_ani"])
    )
    if extra_weights is not None:
        extra = extra_weights.set_index("genome").iloc[:, 0]
        score = score + df["genome"].map(extra).fillna(0.0)
    df["score"] = score
    return df


def pick_winners(sdb_full: pd.DataFrame) -> pd.DataFrame:
    """Argmax score within each secondary cluster; ties break by genome name
    (deterministic). One global sort + head(1) per group — the per-cluster
    Python loop this replaces was O(clusters) pandas calls, minutes at the
    100k-genome scale this stage must handle."""
    top = (
        sdb_full.sort_values(
            ["secondary_cluster", "score", "genome"], ascending=[True, False, True]
        )
        .groupby("secondary_cluster", sort=True)
        .head(1)
    )
    return pd.DataFrame(
        {
            "genome": top["genome"].to_numpy(),
            "cluster": top["secondary_cluster"].to_numpy(),
            "score": top["score"].to_numpy(),
        }
    )


def score_and_pick(
    cdb: pd.DataFrame,
    stats: pd.DataFrame,
    ndb: pd.DataFrame,
    quality: pd.DataFrame | None = None,
    extra_weights: pd.DataFrame | None = None,
    **kwargs,
) -> tuple[pd.DataFrame, pd.DataFrame]:
    """(scored table, winners) — the choose stage's core, shared by the
    batch pipeline (d_choose_wrapper) and the incremental genome index
    (drep_tpu/index/update.py, which re-scores only touched clusters).
    Scores are row-local (own stats + centrality to co-members), so
    calling this on a subset of clusters yields exactly the rows a full
    run would — the property the index's incremental==from-scratch
    invariant leans on."""
    sdb_full = score_genomes(cdb, stats, quality, ndb, extra_weights=extra_weights, **kwargs)
    return sdb_full, pick_winners(sdb_full)


def d_choose_wrapper(wd: WorkDirectory, bdb: pd.DataFrame, **kwargs) -> pd.DataFrame:
    """Score + pick winners; stores Sdb/Wdb; copies winners; returns Wdb."""
    logger = get_logger()
    # `choose/tables` is every table read and written here; the score (with
    # the centrality inside it) and the winners' copy have spans of their own
    with counters.span("choose/tables"):
        cdb = wd.get_db("Cdb")
        ndb = wd.get_db("Ndb") if wd.hasDb("Ndb") else schemas.empty("Ndb")
        stats = wd.get_db("genomeInformation")
        quality = wd.get_db("genomeInfo") if wd.hasDb("genomeInfo") else None
        extra = None
        if kwargs.get("extra_weight_table"):
            extra = pd.read_csv(kwargs["extra_weight_table"], sep=None, engine="python")

    with counters.span("choose/score", genomes=len(cdb)):
        sdb_full, wdb = score_and_pick(cdb, stats, ndb, quality, extra_weights=extra, **kwargs)
    sdb = sdb_full[["genome", "score"]].copy()
    # the reference ABORTS dereplicate without quality info; we proceed with
    # the quality terms scoring 0 (documented delta) — but the Sdb must say
    # so, or a downstream reader would take the scores as quality-informed
    sdb["quality_informed"] = quality is not None
    with counters.span("choose/tables"):
        wd.store_db(schemas.validate(sdb, "Sdb"), "Sdb")
        wd.store_db(schemas.validate(wdb, "Wdb"), "Wdb")

    out_dir = wd.get_loc("dereplicated_genomes")
    loc = bdb.set_index("genome")["location"]
    with counters.span("choose/copy", winners=len(wdb)):
        for row in wdb.itertuples():
            src = loc.get(row.genome)
            if src is not None:
                shutil.copy(src, out_dir)
    logger.info("choose: %d winners from %d genomes", len(wdb), len(cdb))
    return wdb

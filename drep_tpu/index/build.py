"""`index build`: create generation 0 of a genome index.

Two front doors:

- **from a completed work directory** (``--work_directory``): snapshot
  the run's sketches (the workdir cache), its retained sparse edge graph
  (Mdb), its cluster labels (Cdb), and its winners — re-scored through
  choose.py's own core with the index's pinned weights so build-time and
  update-time scoring can never drift. The batch pipeline stays the bulk
  loader; the index is where its output starts serving traffic.
- **from FASTA paths** (``-g``): bootstrap an index with no prior run —
  the whole input set is admitted as generation 0 through the exact
  update machinery (sketch -> full-triangle compare -> cluster -> score),
  which by construction equals a from-scratch run.

Service-mode scope (refused loudly at build): TPU-native engines only
(primary jax_mash / S_algorithm jax_ani), clusterAlg average|single (the
streaming-family linkages the sparse edge graph supports), no
SkipMash/SkipSecondary/greedy/multiround/tertiary, and quality-uninformed
scoring (no genomeInfo) — each of these would break the pinned
incremental==from-scratch invariant in a way the index cannot detect.
"""

from __future__ import annotations

import numpy as np
import pandas as pd

from drep_tpu.errors import UserInputError
from drep_tpu.index.store import IndexStore, LoadedIndex, empty_index
from drep_tpu.index.update import publish_generation, rect_compare, recluster, sketch_batch, _admit_batch
from drep_tpu.utils.logger import get_logger

# the scoring weights an index pins at build (choose.py SCORE_DEFAULTS
# minus S_ani, which rides in params directly)
_WEIGHT_KEYS = (
    "completeness_weight", "contamination_weight",
    "strain_heterogeneity_weight", "N50_weight", "size_weight",
    "centrality_weight",
)

_UNSUPPORTED_SNAPSHOT_FLAGS = (
    "SkipMash", "SkipSecondary", "greedy_secondary_clustering",
    "multiround_primary_clustering", "run_tertiary_clustering",
)


def _refuse_federated_root(index_loc: str) -> None:
    from drep_tpu.index import meta as fedmeta

    if fedmeta.is_federated(index_loc):
        raise UserInputError(
            f"{index_loc} already holds a FEDERATED index "
            f"({fedmeta.META_NAME}); `index update` grows it — build "
            f"refuses to overwrite"
        )


def resolve_params(**kwargs) -> dict:
    """The index's pinned parameter set, from CLUSTER_DEFAULTS/
    SCORE_DEFAULTS/FILTER_DEFAULTS with explicit overrides."""
    from drep_tpu.choose import SCORE_DEFAULTS
    from drep_tpu.cluster.controller import CLUSTER_DEFAULTS
    from drep_tpu.evaluate import EVALUATE_DEFAULTS
    from drep_tpu.filter import FILTER_DEFAULTS

    def pick(key, default):
        v = kwargs.get(key)
        return default if v is None else v

    alg = pick("clusterAlg", CLUSTER_DEFAULTS["clusterAlg"])
    if alg not in ("average", "single"):
        raise UserInputError(
            f"index service mode supports --clusterAlg average or single "
            f"(the sparse-edge-graph linkages), not {alg!r}"
        )
    s_alg = pick("S_algorithm", CLUSTER_DEFAULTS["S_algorithm"])
    if s_alg != "jax_ani":
        raise UserInputError(
            f"index service mode runs the TPU-native secondary only "
            f"(--S_algorithm jax_ani), not {s_alg!r}"
        )
    return {
        "P_ani": float(pick("P_ani", CLUSTER_DEFAULTS["P_ani"])),
        "S_ani": float(pick("S_ani", CLUSTER_DEFAULTS["S_ani"])),
        "cov_thresh": float(pick("cov_thresh", CLUSTER_DEFAULTS["cov_thresh"])),
        "clusterAlg": alg,
        "S_algorithm": s_alg,
        "sketch_size": int(pick("MASH_sketch", CLUSTER_DEFAULTS["MASH_sketch"])),
        "scale": int(pick("scale", CLUSTER_DEFAULTS["scale"])),
        "kmer_size": int(pick("kmer_size", CLUSTER_DEFAULTS["kmer_size"])),
        "hash": pick("hash", CLUSTER_DEFAULTS["hash"]),
        "warn_dist": float(pick("warn_dist", EVALUATE_DEFAULTS["warn_dist"])),
        "filter_length": int(pick("length", FILTER_DEFAULTS["length"])),
        "streaming_block": int(pick("streaming_block", CLUSTER_DEFAULTS["streaming_block"])),
        "weights": {k: float(pick(k, SCORE_DEFAULTS[k])) for k in _WEIGHT_KEYS},
    }


def _params_from_workdir(wd) -> dict:
    """Pin the index params to what the source run ACTUALLY used (its
    cluster/filter argument snapshots), refusing unsupported modes."""
    snap = wd.get_arguments("cluster")
    if snap is None:
        raise UserInputError(
            f"workdir {wd.location} has no cluster argument snapshot — "
            f"build the index from a COMPLETED compare/dereplicate run"
        )
    bad = [f for f in _UNSUPPORTED_SNAPSHOT_FLAGS if snap.get(f)]
    if bad:
        raise UserInputError(
            f"the source run used {bad} — index service mode does not "
            f"support these clustering modes (they break the pinned "
            f"incremental==from-scratch invariant)"
        )
    filt = wd.get_arguments("filter") or {}
    resolved = snap.get("primary_estimator_resolved")
    if resolved is not None and resolved != "streaming_sort":
        get_logger().warning(
            "index build: the source run's primary took the route %r; "
            "incremental updates always compare with the streaming tiles, "
            "which take the distance's logarithm in float32 on the device: "
            "snapshot edges and update edges are the same estimator and "
            "differ by that rounding (up to 2e-6 against the dense routes' "
            "4e-9 on the chip; run the source with --streaming_primary for "
            "equal bytes)", resolved,
        )
    return resolve_params(
        P_ani=snap.get("P_ani"), S_ani=snap.get("S_ani"),
        cov_thresh=snap.get("cov_thresh"), clusterAlg=snap.get("clusterAlg"),
        S_algorithm=snap.get("S_algorithm"), MASH_sketch=snap.get("MASH_sketch"),
        scale=snap.get("scale"), kmer_size=snap.get("kmer_size"),
        hash=snap.get("hash"), warn_dist=snap.get("warn_dist"),
        length=filt.get("length", 0),
    )


def _edges_from_mdb(mdb: pd.DataFrame, name_to_idx: dict[str, int], keep: float):
    """Mdb rows -> the canonical unique (i < j, dist <= keep) edge arrays.
    Handles both Mdb shapes: the sparse streaming table (both directions +
    diagonal) and the dense reference table (all ordered pairs)."""
    g1 = mdb["genome1"].map(name_to_idx).to_numpy()
    g2 = mdb["genome2"].map(name_to_idx).to_numpy()
    # float32 is the streaming path's native dtype; the CSV round-trip
    # preserves it (numpy's shortest-repr floats re-parse exactly)
    dd = mdb["dist"].to_numpy().astype(np.float32)
    ii = np.minimum(g1, g2)
    jj = np.maximum(g1, g2)
    sel = (ii < jj) & (dd <= np.float32(keep))
    ii, jj, dd = ii[sel], jj[sel], dd[sel]
    order = np.lexsort((jj, ii))
    ii, jj, dd = ii[order], jj[order], dd[order]
    # collapse the two stored directions to one row each
    if len(ii):
        first = np.ones(len(ii), bool)
        first[1:] = (ii[1:] != ii[:-1]) | (jj[1:] != jj[:-1])
        ii, jj, dd = ii[first], jj[first], dd[first]
    return ii.astype(np.int64), jj.astype(np.int64), dd


def build_from_workdir(index_loc: str, wd_loc: str) -> dict:
    from drep_tpu.choose import score_and_pick
    from drep_tpu.ingest import _load
    from drep_tpu.parallel.streaming import retention_bound
    from drep_tpu.workdir import WorkDirectory

    logger = get_logger()
    store = IndexStore(index_loc)
    _refuse_federated_root(index_loc)
    if store.exists():
        raise UserInputError(
            f"{index_loc} already holds an index (generation "
            f"{store.read_manifest()['generation']}); `index update` grows "
            f"it — build refuses to overwrite"
        )
    wd = WorkDirectory(wd_loc)
    for table in ("Cdb", "Mdb", "Bdb"):
        if not wd.hasDb(table):
            raise UserInputError(
                f"workdir {wd_loc} has no {table} — build the index from a "
                f"COMPLETED compare/dereplicate run"
            )
    if wd.hasDb("genomeInfo"):
        raise UserInputError(
            "the source run scored with genome quality (genomeInfo); index "
            "service mode scores quality-uninformed (new genomes arrive "
            "with no quality data) — build from a run without genomeInfo"
        )
    params = _params_from_workdir(wd)
    if not wd.has_arrays("sketches"):
        raise UserInputError(
            f"workdir {wd_loc} has no sketch cache (data/arrays/"
            f"sketches.npz) — the index snapshots sketches, not FASTAs"
        )
    gs = _load(wd, params["kmer_size"], params["sketch_size"], params["scale"])
    cdb = wd.get_db("Cdb")
    if sorted(gs.names) != sorted(cdb["genome"]):
        raise UserInputError(
            f"workdir {wd_loc}: sketch cache and Cdb cover different genome "
            f"sets — the run is stale or partially resumed; rerun it"
        )
    bdb = wd.get_db("Bdb").set_index("genome")["location"]

    idx = empty_index(params, location=store.location)
    idx.names = list(gs.names)
    idx.locations = [str(bdb.get(g, "")) for g in gs.names]
    idx.gdb = gs.gdb.reset_index(drop=True)
    idx.admitted = np.zeros(len(gs.names), np.int64)
    idx.bottom = list(gs.bottom)
    idx.scaled = list(gs.scaled)

    cutoff = 1.0 - params["P_ani"]
    keep = retention_bound(cutoff, params["warn_dist"], params["clusterAlg"])
    name_to_idx = {g: i for i, g in enumerate(gs.names)}
    idx.edges = _edges_from_mdb(wd.get_db("Mdb"), name_to_idx, keep)

    # labels: the snapshot — Cdb in index genome order
    by_genome = cdb.set_index("genome")
    idx.primary = np.array(
        [int(by_genome.loc[g, "primary_cluster"]) for g in gs.names], np.int64
    )
    suffixes = []
    for g in gs.names:
        sec = str(by_genome.loc[g, "secondary_cluster"])
        try:
            suffixes.append(int(sec.rsplit("_", 1)[1]))
        except (IndexError, ValueError) as e:
            raise UserInputError(
                f"Cdb secondary_cluster {sec!r} is not 'P_S'-shaped — "
                f"unsupported clustering output for service mode"
            ) from e
    idx.suffix = np.array(suffixes, np.int64)

    # scores + winners: re-derived through the choose core with the
    # index's pinned weights (NOT copied from Sdb — a run scored with
    # custom CLI weights would silently disagree with every later update)
    from drep_tpu import schemas

    ndb = wd.get_db("Ndb") if wd.hasDb("Ndb") else schemas.empty("Ndb")
    stats = idx.gdb[["genome", "length", "N50"]]
    cdb_idx = pd.DataFrame(
        {"genome": idx.names, "secondary_cluster": idx.secondary_names()}
    )
    from drep_tpu.utils.profiling import counters

    with counters.span("index/score", members=idx.n):
        sdb_full, wdb = score_and_pick(
            cdb_idx, stats, ndb, None, S_ani=params["S_ani"], **params["weights"]
        )
    counters.add_index(score_calls=1)
    by_score = sdb_full.set_index("genome")["score"]
    idx.score = np.array([float(by_score[g]) for g in idx.names], np.float64)
    idx.winners = wdb[["cluster", "genome", "score"]]

    publish_generation(store, idx, 0, 0, idx.edges)
    logger.info(
        "index build: snapshotted %d genomes / %d primary clusters from %s "
        "-> %s (generation 0)",
        idx.n, int(idx.primary.max()) if idx.n else 0, wd_loc, index_loc,
    )
    return {
        "n_genomes": idx.n, "generation": 0,
        "primary_clusters": int(idx.primary.max()) if idx.n else 0,
        "secondary_clusters": int(cdb_idx["secondary_cluster"].nunique()),
    }


def build_from_paths(
    index_loc: str, genome_paths: list[str], processes: int = 1, **kwargs
) -> dict:
    """Bootstrap build: the whole input set is generation 0's batch,
    admitted through the exact update machinery."""
    from drep_tpu.utils.profiling import counters

    store = IndexStore(index_loc)
    _refuse_federated_root(index_loc)
    if store.exists():
        raise UserInputError(
            f"{index_loc} already holds an index; `index update` grows it — "
            f"build refuses to overwrite"
        )
    params = resolve_params(**kwargs)
    idx = empty_index(params, location=store.location)
    with counters.span("index/sketch", genomes=len(genome_paths)):
        batch, results = sketch_batch(idx, genome_paths, processes=processes)
    if not len(batch):
        raise UserInputError("no genomes survived the length filter — nothing to index")
    with counters.span("index/admit", genomes=len(batch)):
        _admit_batch(idx, batch, results, 0)
    ii, jj, dd, _pairs = rect_compare(idx, 0, store.pending_dir(0))
    idx.edges = (ii, jj, dd)
    summary = recluster(idx, 0, processes=processes)
    publish_generation(store, idx, 0, 0, idx.edges)
    get_logger().info(
        "index build: %d genomes -> %s (generation 0, %d primary / %d "
        "secondary clusters)",
        idx.n, index_loc, summary["primary_clusters"], summary["secondary_clusters"],
    )
    return {"n_genomes": idx.n, "generation": 0, **summary}

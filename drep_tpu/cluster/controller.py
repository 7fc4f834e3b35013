"""Cluster-stage orchestration: Bdb -> Mdb -> Ndb -> Cdb.

Reference parity: drep/d_cluster/controller.py::d_cluster_wrapper
(SURVEY.md §3.2; reference mount empty, upstream layout):

- resume: if the workdir already holds Cdb and the stored cluster arguments
  match, skip recompute entirely (§3.5 / §5.4).
- PRIMARY: all-vs-all MinHash distance -> hierarchical clustering at
  cutoff 1-P_ani -> integer primary clusters (Mdb).
- SECONDARY: per primary cluster with >1 member, pairwise ANI ->
  coverage-gated hierarchical clustering at 1-S_ani -> "P_S" string ids
  (Ndb); or greedy-incremental representative clustering at scale.
- Cdb assembly with threshold/cluster_method/comparison_algorithm columns.

Execution differs from the reference by design: no subprocess/file
round-trips — sketches are packed once and all-pairs tiles run on device
(BASELINE.json north star).
"""

from __future__ import annotations

import contextlib
import os
import pickle
from typing import Any

import numpy as np
import pandas as pd

from drep_tpu import schemas
from drep_tpu.cluster import dispatch, pairs
from drep_tpu.cluster import engines  # noqa: F401 — registers built-in engines
from drep_tpu.ingest import (
    DEFAULT_SCALE,
    DEFAULT_SKETCH_SIZE,
    GenomeSketches,
    IngestPass,
    read_genomes,
    sketch_cache_will_hit,
    sketch_genomes,
)
from drep_tpu.ops.kmers import DEFAULT_K
from drep_tpu.ops.linkage import (
    cluster_by_components,
    cluster_hierarchical,
    single_linkage_device,
)
from drep_tpu.utils.logger import get_logger
from drep_tpu.utils.profiling import counters
from drep_tpu.workdir import WorkDirectory

CLUSTER_DEFAULTS: dict[str, Any] = {
    "P_ani": 0.9,
    "S_ani": 0.95,
    "cov_thresh": 0.1,
    "clusterAlg": "average",
    "primary_algorithm": "jax_mash",
    "S_algorithm": "jax_ani",
    "MASH_sketch": DEFAULT_SKETCH_SIZE,
    "scale": DEFAULT_SCALE,
    "kmer_size": DEFAULT_K,
    "hash": "splitmix64",
    "processes": 1,
    "SkipMash": False,
    "SkipSecondary": False,
    "greedy_secondary_clustering": False,
    "run_tertiary_clustering": False,
    "multiround_primary_clustering": False,
    "primary_chunksize": 5000,
    "mdb_dense_limit": 2000,
    "mesh_shape": None,
    "streaming_primary": False,
    "streaming_block": 1024,
    "streaming_threshold": 30_000,
    # LSH-banded candidate pruning (ops/lsh.py): "lsh" makes the streaming
    # primary's tile walk sparse (only tiles holding a candidate pair are
    # dispatched — recall 1.0 at the retention bound by construction, so
    # retained edges are bit-identical either way). Off by default until
    # the equivalence suite has aged on real data; never a _RESUME_KEY
    # (results identical) — but the streaming checkpoint meta pins the
    # banding params, so a MID-RUN knob change refuses to resume loudly.
    "primary_prune": "off",
    "prune_bands": 0,
    "prune_min_shared": 0,
    # memory bound (in codes) for the LSH bucket join's host expansion:
    # 0 = one np.unique over the whole expansion (fine to ~1M genomes on
    # a fat host); > 0 = chunked incremental fold, identical candidate
    # set (property-tested), for thin hosts beyond that. Pure execution
    # knob — never pinned in checkpoint meta, never a _RESUME_KEY.
    "prune_join_chunk": 0,
    "overlap_ingest": True,
    # fault tolerance (parallel/faulttol.py): retries per failed device
    # dispatch, the per-dispatch watchdog (seconds; 0 = auto-derived from
    # the run's own tile latencies), and how many pod-member deaths the
    # elastic streaming protocol tolerates before aborting. None affects
    # results, only how failures are survived — kept out of _RESUME_KEYS
    # so changing them never invalidates a workdir.
    "fault_retries": 2,
    "dispatch_timeout": 0.0,
    "max_dead_processes": 1,
    # scale-UP elasticity (ISSUE 9): mid-run join admissions the elastic
    # pod accepts (0 = refused), and the graceful-preemption grace window
    # (SIGTERM -> planned departure at the next safe boundary; the grace
    # timer force-exits 0 if nothing consumes the flag). Membership churn
    # never changes results (bit-identical by the canonical epoch-0
    # assembly), so neither is a _RESUME_KEY.
    "max_joins": 0,
    "drain_grace_s": 30.0,
    # durable-I/O knobs (utils/durableio.py): transient shared-FS retry
    # budget (None = DREP_TPU_IO_RETRIES / default 3) and fsync-on-publish
    # (False = DREP_TPU_FSYNC). Pure durability policy — never results —
    # so neither joins _RESUME_KEYS.
    "io_retries": None,
    "fsync": False,
}

_RESUME_KEYS = [
    "P_ani",
    "S_ani",
    "cov_thresh",
    "clusterAlg",
    "primary_algorithm",
    "S_algorithm",
    "MASH_sketch",
    "scale",
    "kmer_size",
    "hash",
    "SkipMash",
    "SkipSecondary",
    "greedy_secondary_clustering",
    "run_tertiary_clustering",
    "streaming_primary",
    "streaming_threshold",  # auto-enables streaming (sparse-graph linkage)
    "warn_dist",  # shapes the sparse Mdb's retention threshold
    "genomes",
]


def _fill_defaults(kwargs: dict[str, Any]) -> dict[str, Any]:
    out = dict(CLUSTER_DEFAULTS)
    out.update({k: v for k, v in kwargs.items() if v is not None})
    return out


def _ft_config(kw: dict[str, Any]):
    """Fault-tolerance knobs -> executor config (also installed as the
    process default so paths that cannot thread a config — the dense
    ring — honor the same CLI flags). --dispatch_timeout 0 enables the
    auto-derived watchdog (k x rolling median tile latency, floored —
    parallel/faulttol.py); an explicit positive value is authoritative,
    a negative value disables the watchdog entirely."""
    from drep_tpu.parallel.faulttol import (
        FaultTolConfig,
        configure_defaults,
        install_drain_handler,
    )

    timeout = float(kw["dispatch_timeout"])
    cfg = FaultTolConfig(
        max_retries=int(kw["fault_retries"]),
        dispatch_timeout_s=max(0.0, timeout),
        auto_timeout=timeout == 0.0,
        max_dead_processes=int(kw["max_dead_processes"]),
        max_joins=int(kw.get("max_joins", 0)),
    )
    configure_defaults(cfg)
    # graceful-preemption wiring (ISSUE 9): SIGTERM -> planned departure
    # at the next stripe/ring-step boundary, force-exit 0 past the grace.
    # Best-effort: library embeddings off the main thread keep their own
    # signal policy (install returns False there).
    install_drain_handler(float(kw.get("drain_grace_s", 30.0)))
    # the storage-side twin: install the run's durable-I/O policy
    # (--io_retries / --fsync; None falls through to the env knobs) so
    # every shard/meta/note publish in the run honors the same budget
    from drep_tpu.utils import durableio

    durableio.configure(
        retries=kw.get("io_retries"), fsync=bool(kw.get("fsync")) or None
    )
    return cfg


def _warn_dist(kw: dict[str, Any]) -> float:
    """warn_dist for sparse-Mdb retention — the evaluate stage's default,
    honoring an explicit 0.0 (warnings disabled)."""
    from drep_tpu.evaluate import EVALUATE_DEFAULTS

    v = kw.get("warn_dist")
    return EVALUATE_DEFAULTS["warn_dist"] if v is None else float(v)


def _mdb_from_dist(
    dist: np.ndarray, names: list[str], dense_limit: int, p_ani: float, warn_dist: float
) -> pd.DataFrame:
    """Pair table from the distance matrix. Dense (all N^2 ordered pairs,
    reference-style) for small N; thresholded sparse beyond `dense_limit`
    so a 100k-genome Mdb does not need 10^10 rows. The sparse threshold
    keeps pairs up to max(1-P_ani, warn_dist) so the evaluate stage still
    sees near-threshold winner pairs."""
    n = len(names)
    if n <= dense_limit:
        ii, jj = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
        ii, jj = ii.ravel(), jj.ravel()
    else:
        keep = dist <= max(1.0 - p_ani, warn_dist)
        np.fill_diagonal(keep, True)
        ii, jj = np.nonzero(keep)
    d = dist[ii, jj]
    arr = np.array(names)
    return pd.DataFrame(
        {"genome1": arr[ii], "genome2": arr[jj], "dist": d, "similarity": 1.0 - d}
    )


def _streaming_mdb(edges, names: list[str]) -> pd.DataFrame:
    """Sparse Mdb from thresholded streaming edges: both directions plus the
    diagonal, matching the thresholded branch of `_mdb_from_dist`."""
    ii, jj, dd = edges
    n = len(names)
    arr = np.array(names)
    g1 = np.concatenate([arr[ii], arr[jj], arr])
    g2 = np.concatenate([arr[jj], arr[ii], arr])
    d = np.concatenate([dd, dd, np.zeros(n, np.float32)])
    return pd.DataFrame({"genome1": g1, "genome2": g2, "dist": d, "similarity": 1.0 - d})


def _primary_route(n: int, kw: dict[str, Any]) -> str:
    """The route the primary of `n` genomes takes on THIS host: 'skipmash',
    'multiround_<dense route of a chunk>', 'streaming_sort', 'ring_sort' or
    'sort'. THE decision: `_primary_clusters` dispatches on this name, the
    resume snapshot records it as `primary_estimator_resolved` (every
    route computes the one sort estimator; the key keeps its name for the
    readers of stored snapshots), and `_ingest_stage` overlaps the tile
    programs' compile where it says 'streaming_sort'."""
    if kw["SkipMash"] or n == 1:
        return "skipmash"
    if kw["multiround_primary_clustering"] and n > kw["primary_chunksize"]:
        return "multiround_" + engines.dense_primary_route(
            kw["primary_chunksize"], kw["mesh_shape"]
        )[0]
    if kw["streaming_primary"] or (
        kw["primary_algorithm"] == "jax_mash" and n >= kw["streaming_threshold"]
    ):
        return "streaming_sort"
    return engines.dense_primary_route(n, kw["mesh_shape"])[0]


def _primary_clusters(
    gs: GenomeSketches,
    bdb: pd.DataFrame,
    kw: dict[str, Any],
    wd: WorkDirectory | None = None,
    ft_cfg=None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, pd.DataFrame | None, int]:
    """Returns (labels 1..C, dist matrix | None, linkage, sparse Mdb | None,
    pairs actually compared — 0 for skipped work, honest across resumes)."""
    logger = get_logger()
    n = len(gs.names)
    route = _primary_route(n, kw)
    if route == "skipmash":
        # reference --SkipMash: everything lands in one primary cluster
        return np.ones(n, dtype=np.int64), np.zeros((n, n), np.float32), np.empty((0, 4)), None, 0
    if route.startswith("multiround_"):
        from drep_tpu.cluster.multiround import multiround_primary_clustering

        labels, pairs_done = multiround_primary_clustering(gs, bdb, kw)
        return labels, None, np.empty((0, 4)), None, pairs_done
    if route == "streaming_sort":
        from drep_tpu.parallel.streaming import streaming_primary_clusters

        if not kw["streaming_primary"]:
            logger.warning(
                "%d genomes >= --streaming_threshold %d: primary stage auto-switches "
                "to the out-of-core streaming path (pass --streaming_primary to opt "
                "in explicitly, or raise the threshold to keep the dense path)",
                n, kw["streaming_threshold"],
            )
        ckpt = wd.get_dir(os.path.join("data", "streaming_primary")) if wd is not None else None
        packed = engines.pack_primary(gs.bottom, gs.names, gs.sketch_size, kw["processes"])
        # --clusterAlg carries into the streaming path: average (default)
        # runs sparse UPGMA over the retained edge graph, single runs
        # connected components; anything else raises with guidance — no
        # silent linkage-family switch at the streaming threshold
        if kw["primary_prune"] not in ("off", "lsh"):
            raise ValueError(
                f"--primary_prune must be off or lsh, not {kw['primary_prune']!r}"
            )
        labels, edges, pairs_computed = streaming_primary_clusters(
            packed,
            gs.k,
            kw["P_ani"],
            block=kw["streaming_block"],
            checkpoint_dir=ckpt,
            keep_dist=_warn_dist(kw),  # evaluate-stage visibility
            cluster_alg=kw["clusterAlg"],
            ft_config=ft_cfg,
            primary_prune=kw["primary_prune"],
            prune_bands=kw["prune_bands"],
            prune_min_shared=kw["prune_min_shared"],
            prune_join_chunk=kw["prune_join_chunk"],
        )
        with counters.span("mdb_build"):
            sparse_mdb = _streaming_mdb(edges, gs.names)
        return labels, None, np.empty((0, 4)), sparse_mdb, pairs_computed
    if kw["primary_prune"] != "off":
        # the dense engines materialize every tile by design — pruning
        # only exists on the streaming schedule (and the index's rect
        # compare); silently "accepting" the flag would misreport
        logger.warning(
            "--primary_prune %s only applies to the streaming primary "
            "(this run resolved to the dense path; lower "
            "--streaming_threshold or pass --streaming_primary) — ignored",
            kw["primary_prune"],
        )
    # 'ring_sort' | 'sort': the engine takes the mesh or the one device
    # by engines.dense_primary_route, the function the name came from
    engine = dispatch.get_primary(kw["primary_algorithm"])
    dist, _sim = engine(
        gs, bdb=bdb, processes=kw["processes"], mesh_shape=kw["mesh_shape"]
    )
    cutoff = 1.0 - kw["P_ani"]
    on_device = kw["clusterAlg"] == "single" and n > 64
    # the tree's one reader is the dendrogram (analyze.py): a job that plots
    # nothing stores the empty tree the streaming path stores (PARITY.md)
    tree = "skipped" if on_device or kw.get("skip_plots", False) else "built"
    link = np.empty((0, 4))
    with counters.span("primary/linkage", genomes=n, tree=tree) as sp:
        if on_device:
            labels = single_linkage_device(dist, cutoff)
        else:
            # the labels are the component route's in either case, so one
            # code path decides Cdb
            labels, did = cluster_by_components(dist, cutoff, method=kw["clusterAlg"])
            counters.add_primary_linkage(tree=tree, **did)
            sp.note(**{k: did[k] for k in ("components", "linkage_calls", "largest")})
            if tree == "built":
                _, link = cluster_hierarchical(dist, cutoff, method=kw["clusterAlg"])
    return labels, dist, link, None, n * (n - 1) // 2


# batching of small clusters: one device call replaces hundreds of
# latency-bound round trips (most primary clusters are tiny at scale)
SMALL_CLUSTER_MAX = 32
BATCH_ROWS_MAX = 512


def _secondary_postprocess(
    gs: GenomeSketches,
    indices: list[int],
    pc: int,
    kw: dict[str, Any],
    ani: np.ndarray,
    cov: np.ndarray,
) -> tuple[pairs.NdbColumns, np.ndarray, np.ndarray]:
    """(ani, cov) for one primary cluster -> (Ndb rows, labels 1.., linkage)."""
    names = [gs.names[i] for i in indices]
    ndb = pairs.directional_ndb_columns(names, ani, cov, pc)
    dist = 1.0 - pairs.gated_symmetric_ani(ani, cov, kw["cov_thresh"])
    labels, link = cluster_hierarchical(dist, 1.0 - kw["S_ani"], method=kw["clusterAlg"])
    return ndb, labels, link


def secondary_for_cluster(
    gs: GenomeSketches,
    bdb: pd.DataFrame,
    indices: list[int],
    pc: int,
    kw: dict[str, Any],
) -> tuple[pairs.NdbColumns, np.ndarray, np.ndarray]:
    """One primary cluster -> (Ndb rows, secondary labels 1.., linkage).

    The secondary stage's per-cluster route, and the incremental genome
    index's (drep_tpu/index/update.py), which re-runs the secondary for
    exactly the primary clusters its update touched — through THIS
    implementation, so a re-scored cluster's (Ndb rows, labels) are
    bit-identical to what a from-scratch run computes for the same
    member set. `kw` needs S_algorithm/S_ani/cov_thresh/clusterAlg/
    processes/mesh_shape (fill via CLUSTER_DEFAULTS). Both callers keep
    the rows as columns and build one frame of many clusters'."""
    engine = dispatch.get_secondary(kw["S_algorithm"])
    ani, cov = engine(gs, indices, bdb=bdb, processes=kw["processes"], mesh_shape=kw["mesh_shape"])
    with counters.span("secondary/post"):
        return _secondary_postprocess(gs, indices, pc, kw, ani, cov)


def _secondary_stage(
    gs: GenomeSketches,
    bdb: pd.DataFrame,
    kw: dict[str, Any],
    ft_cfg,
    wd: WorkDirectory,
    snapshot: dict[str, Any],
    primary: np.ndarray,
    n_primary: int,
) -> tuple[dict[str, str], list[pairs.NdbColumns], dict[int, dict[str, Any]]]:
    """The secondary (ANI) stage over every primary cluster: ({genome:
    "P_S" secondary name}, each cluster's Ndb rows as columns, in cluster
    order (pairs.assemble_ndb makes the table of them), {primary cluster:
    its linkage and names} for Clustering_files)."""
    import jax

    from drep_tpu.cluster.secondary_ckpt import SecondaryCheckpoint
    from drep_tpu.parallel.faulttol import (
        drain_at_boundary,
        pod_dead,
        pod_epoch,
        pod_live,
        retrying_call,
    )
    from drep_tpu.utils import faults

    secondary_names: dict[str, str] = {}
    greedy = kw["greedy_secondary_clustering"]
    # the batched route stays available under greedy: small clusters
    # get their (ani, cov) from ONE device call covering many
    # clusters, then the greedy assignment runs host-side on those
    # matrices with identical semantics (greedy.py::
    # greedy_assign_from_matrices) — 35k per-cluster greedy engine
    # invocations at the 100k scale were measured pathologically
    # slower than the batch route. Restricted to jax_ani: the greedy
    # engine hardcodes containment-ANI numerics, so a batched variant
    # of any OTHER algorithm must not silently substitute its numbers
    # for small clusters only
    batched_fn = (
        dispatch.get_secondary_batched(kw["S_algorithm"])
        if not greedy or kw["S_algorithm"] == "jax_ani"
        else None
    )
    # one O(n) pass — a per-cluster membership scan would be
    # O(n_clusters * n), 35M Python iterations at 10k genomes
    members: dict[int, list[int]] = {}
    for i, pc in enumerate(primary):
        members.setdefault(int(pc), []).append(i)
    multi = []
    for pc in range(1, n_primary + 1):
        indices = members.get(pc, [])
        if len(indices) == 1:
            secondary_names[gs.names[indices[0]]] = f"{pc}_1"
        elif indices:
            multi.append((pc, indices))

    # warn_dist shapes only the Mdb retention, never secondary results;
    # the primary's route never touches ANI numerics — keep
    # both out of the checkpoint key so neither a warning-threshold
    # change nor a device-count change throws away the whole ANI stage
    sec_snapshot = {
        k: v for k, v in snapshot.items()
        if k not in ("warn_dist", "primary_estimator_resolved")
    }
    # every touch of the checkpoint store is a `secondary/checkpoint` span
    # of its own, where the work is: a cluster is looked up just before it
    # is computed and saved right after its post-process, so a kill loses
    # one cluster. Two spans a cluster: thousands a job, under the 10^4 a
    # span site may reach.
    with counters.span("secondary/checkpoint"):
        ckpt = SecondaryCheckpoint(
            wd.get_dir(os.path.join("data", "secondary_checkpoints")),
            sec_snapshot, primary, gs.names,
        )
    results: dict[int, tuple[pairs.NdbColumns, np.ndarray, np.ndarray]] = {}
    alone = jax.process_count() == 1

    def publish(pc: int) -> None:
        """Save cluster `pc`'s checkpoint; a one-process job with a drain
        pending leaves right after it (a pod's members stay: their peers
        hold the same loop)."""
        with counters.span("secondary/checkpoint"):
            ckpt.save(pc, *results[pc])
        faults.fire("secondary_checkpoint")
        if alone and ckpt.dir is not None:
            drain_at_boundary("secondary", clusters_published=len(results), last_cluster=pc)

    small: list[tuple[int, list[int]]] = []
    for pc, indices in multi:
        m = len(indices)
        with counters.span("secondary/checkpoint"):
            cached = ckpt.load(pc)
        if cached is not None:
            results[pc] = cached  # resumed: 0 pairs counted
            counters.add_resume(clusters_resumed=1)
        elif batched_fn is not None and m <= SMALL_CLUSTER_MAX:
            small.append((pc, indices))  # one device call for many
        elif greedy:
            from drep_tpu.cluster.greedy import greedy_secondary_cluster

            with counters.stage("secondary_compare"):
                ndb, labels = greedy_secondary_cluster(gs, bdb, indices, pc, kw)
            counters.stages["secondary_compare"].pairs += len(ndb)  # actual comparisons made
            counters.add_resume(clusters_computed=1)
            results[pc] = (ndb, labels, np.empty((0, 4)))
            publish(pc)
        else:
            with counters.stage("secondary_compare", pairs=m * (m - 1) // 2):
                # a transient device failure on one big cluster must
                # not kill a run that already banked thousands of
                # per-cluster checkpoint shards — bounded retries,
                # same knobs as the streaming tile executor.
                # local_only: the secondary engines clamp their mesh
                # to this process's devices on pods (engines.py), so
                # a per-process retry cannot desync the pod — a
                # mid-batch failure retries instead of killing the run
                results[pc] = retrying_call(
                    lambda indices=indices, pc=pc: secondary_for_cluster(
                        gs, bdb, indices, pc, kw
                    ),
                    site="secondary_batch",
                    config=ft_cfg,
                    local_only=True,
                )
            counters.add_resume(clusters_computed=1)
            publish(pc)

    # flush the small clusters in row-bounded batches
    batches: list[list[tuple[int, list[int]]]] = []
    rows = BATCH_ROWS_MAX + 1  # force a new batch on the first item
    for item in small:
        if rows + len(item[1]) > BATCH_ROWS_MAX:
            batches.append([])
            rows = 0
        batches[-1].append(item)
        rows += len(item[1])
    for batch in batches:
        # under greedy the counter means "comparisons the greedy scan
        # consumed" (len(ndb)) on BOTH routes, so the reported number
        # does not depend on whether a cluster rode the batched or the
        # per-cluster path; without greedy it is true all-pairs work
        pairs_in_batch = (
            0 if greedy
            else sum(len(ix) * (len(ix) - 1) // 2 for _, ix in batch)
        )
        with counters.stage("secondary_compare", pairs=pairs_in_batch):
            outs = retrying_call(
                lambda batch=batch: batched_fn(
                    gs,
                    [ix for _, ix in batch],
                    processes=kw["processes"],
                    mesh_shape=kw["mesh_shape"],
                ),
                site="secondary_batch",
                config=ft_cfg,
                # process-local by the secondary-mesh contract
                # (engines._mesh_or_none local_only): retryable on pods
                local_only=True,
            )
        counters.add_resume(clusters_computed=len(batch))
        with counters.stage("secondary_postprocess"):
            for (pc, indices), (ani, cov) in zip(batch, outs, strict=True):
                if greedy:
                    from drep_tpu.cluster.greedy import greedy_assign_from_matrices

                    ndb, labels = greedy_assign_from_matrices(gs, indices, pc, kw, ani, cov)
                    counters.stages["secondary_compare"].pairs += len(ndb)
                    results[pc] = (ndb, labels, np.empty((0, 4)))
                else:
                    results[pc] = _secondary_postprocess(gs, indices, pc, kw, ani, cov)
                publish(pc)

    if pod_live() is not None and ckpt.dir is not None:
        # the pod lost member(s) somewhere before/inside the secondary
        # loop: stamp the degradation provenance into the secondary
        # checkpoint store's meta (same contract as the streaming and
        # ring stores — extra keys never invalidate a resume), stamped
        # by the lowest live process only so replicated survivors do
        # not race the read-modify-write
        import jax

        from drep_tpu.utils.ckptmeta import stamp_checkpoint_meta

        if jax.process_index() == min(pod_live()):
            stamp_checkpoint_meta(
                ckpt.dir,
                {"pod_epochs": pod_epoch() + 1, "dead_processes": pod_dead()},
            )
    ndb_parts: list[pairs.NdbColumns] = []
    files: dict[int, dict[str, Any]] = {}
    for pc, indices in multi:  # assemble in cluster order (deterministic)
        ndb, labels, link = results[pc]
        ndb_parts.append(ndb)
        files[pc] = {"linkage": link, "names": [gs.names[i] for i in indices]}
        for idx, lab in zip(indices, labels):
            secondary_names[gs.names[idx]] = f"{pc}_{lab}"
    with counters.span("secondary/checkpoint"):
        ckpt.finish(n_primary)
    return secondary_names, ndb_parts, files


def _sketch_args(kw: dict[str, Any]) -> dict[str, Any]:
    return {"k": kw["kmer_size"], "sketch_size": kw["MASH_sketch"], "scale": kw["scale"],
            "processes": kw["processes"], "hash_name": kw["hash"]}


@contextlib.contextmanager
def _ingest_stage(wd: WorkDirectory, genomes, kw: dict[str, Any], route: str):
    """`stage:ingest_or_cache` (counted, so a run's stage seconds attribute
    the cache-load / ingest wall separately from compute), with the
    streaming tile programs' cold compile hidden behind it where that buys
    anything. `route` is the run's :func:`_primary_route`."""
    warmup_thread = None
    warmup_error: list[BaseException] = []
    if (
        kw["overlap_ingest"]
        # ingest pool workers are SPAWNED (ingest.py::read_genomes), so
        # running them while this thread sits inside XLA's multithreaded
        # compiler is safe — spawn children inherit no locks
        and route == "streaming_sort"
        # nothing to hide the compile behind when ingest will return
        # without sketching (whole-run cache hit on resumed runs /
        # pre-planted workdirs, or a shard store that already covers
        # every genome after a kill between the last flush and cache
        # assembly): the main thread then just waits on the same compile.
        # Read-only pre-check; the revalidation inside sketch_genomes
        # still governs whether the cache is actually used
        and not sketch_cache_will_hit(
            wd, genomes, kw["kmer_size"], kw["MASH_sketch"],
            kw["scale"], kw["hash"],
        )
    ):
        # overlap the streaming tile programs' cold compile with host
        # ingest — the one ingest/compute overlap that is exact and free
        # (parallel/streaming.py module docstring has the analysis);
        # compile only, nothing executes
        import threading

        from drep_tpu.parallel.streaming import (
            retention_bound,
            warmup_streaming_compile,
        )

        def _warm() -> None:
            # this thread is the first code to touch the backend: whatever
            # it raises (no device, a compiler rejection) must reach the
            # run, so it is kept and re-raised after the join
            try:
                warmup_streaming_compile(
                    kw["MASH_sketch"], block=kw["streaming_block"],
                    k=kw["kmer_size"],
                    cutoff=retention_bound(
                        1.0 - kw["P_ani"], _warn_dist(kw), kw["clusterAlg"]
                    ),
                )
            except BaseException as e:  # noqa: BLE001 — re-raised below
                warmup_error.append(e)

        warmup_thread = threading.Thread(target=_warm, name="drep-warmup")
        warmup_thread.start()
    try:
        with counters.stage("ingest_or_cache"):
            yield
    finally:
        if warmup_thread is not None:
            # joined even when ingest raises — a dangling thread inside
            # XLA's C++ compile aborts interpreter teardown and masks the
            # real error; by now ingest has absorbed the compile anyway
            warmup_thread.join()
    if warmup_error:
        raise warmup_error[0]


def read_for_filter(wd: WorkDirectory, bdb: pd.DataFrame, stats_only, **kwargs) -> IngestPass:
    """`dereplicate`'s one read of every FASTA, in a `stage:ingest_or_cache`
    of its own between the filter's two halves (filter.py): `stats_only`
    are the genomes the quality table already drops. The pass goes to
    :func:`d_cluster_wrapper` as `sketches`."""
    kw = _fill_defaults(kwargs)
    route = _primary_route(len(bdb) - len(stats_only), kw)
    with _ingest_stage(wd, bdb["genome"], kw, route):
        return read_genomes(bdb, wd=wd, stats_only=stats_only, **_sketch_args(kw))


def _hold_for_evaluate(wd: WorkDirectory, df: pd.DataFrame, table: str) -> None:
    """Leave `stage:evaluate` what it reads of the pair table `df`, just
    stored as `table`: name codes and value columns (evaluate.PairColumns),
    not the frame's strings. The job that computed a table does not read it
    back; a resumed work directory holds nothing and reads the file."""
    from drep_tpu.evaluate import PairColumns

    with counters.span("evaluate/columns", rows=len(df)):
        wd.hold(table, PairColumns.of(df, table, held=True))


def d_cluster_wrapper(
    wd: WorkDirectory, bdb: pd.DataFrame, sketches: IngestPass | None = None, **kwargs
) -> pd.DataFrame:
    """Run (or resume) the full clustering stage; returns Cdb. `sketches`
    is the filter's pass over the FASTAs where there was one: `bdb`'s
    genomes are kept from it and none is read here."""
    logger = get_logger()
    kw = _fill_defaults(kwargs)
    ft_cfg = _ft_config(kw)  # install the run's fault-tolerance defaults
    from drep_tpu.parallel.allpairs import configure_ring

    # run-wide dense-ring execution config: the step-wise ring checkpoints
    # its per-step block tiles under the workdir (lazily — the directory
    # is only created when a mesh ring actually runs), making the dense
    # primary/secondary rings kill-resumable and pod-death elastic.
    configure_ring(checkpoint_base=os.path.join(wd.location, "data", "dense_ring"))
    snapshot = {k: kw.get(k) for k in _RESUME_KEYS if k != "genomes"}
    # normalize: CLI passes 0.25 explicitly, library callers omit it — the
    # effective value must snapshot identically from both entry points
    snapshot["warn_dist"] = _warn_dist(kw)
    snapshot["genomes"] = sorted(bdb["genome"])

    # the route the primary takes HERE (it depends on N and on this host's
    # device count). Stored for boundary detection, excluded from the match
    # keys — a changed route must warn, not recompute: the routes compute
    # the one estimator, and a ring's bytes differ from a streaming run's
    # by float32 rounding.
    route = _primary_route(len(bdb), kw)
    snapshot["primary_estimator_resolved"] = route
    match_keys = [k for k in snapshot if k != "primary_estimator_resolved"]

    if wd.hasDb("Cdb") and wd.arguments_match("cluster", snapshot, keys=match_keys):
        stored = wd.get_arguments("cluster") or {}
        stored_resolved = stored.get("primary_estimator_resolved")
        if stored_resolved is not None and stored_resolved != route:
            logger.warning(
                "resuming a workdir whose primary took the route %r, but this run "
                "would take %r (N, the device count or a flag crossed a route "
                "boundary). The cached tables are kept: they are that route's; "
                "delete Cdb/Mdb to recompute on this one.",
                stored_resolved, route,
            )
        logger.info("resuming: Cdb present with matching cluster arguments — skipping recompute")
        return wd.get_db("Cdb")

    if sketches is not None:
        # the filter's pass read every FASTA already: its sketches of the
        # kept genomes become this run's cache, nothing is sketched here
        with counters.stage("ingest_or_cache"):
            gs = sketches.keep(bdb["genome"])
    else:
        with _ingest_stage(wd, bdb["genome"], kw, route):
            gs = sketch_genomes(bdb, wd=wd, **_sketch_args(kw))
    n = len(gs.names)
    logger.info("clustering %d genomes (primary=%s, secondary=%s)", n, kw["primary_algorithm"], kw["S_algorithm"])

    import time as _time

    t0 = _time.perf_counter()
    # counters.add below keeps the stage's totals (counters.stage cannot
    # wrap this site — pairs_done is only known after the call); the span
    # is the container of the primary/* phases
    from drep_tpu.parallel.faulttol import PodDrained, pod_dead, pod_epoch, pod_live

    try:
        with counters.span("stage:primary_compare"):
            primary, pdist, plink, sparse_mdb, pairs_done = _primary_clusters(
                gs, bdb, kw, wd=wd, ft_cfg=ft_cfg
            )
    except PodDrained as drained:  # the stage as far as it came, in the attempt's record
        counters.add("primary_compare", pairs=drained.pairs, seconds=_time.perf_counter() - t0)
        raise
    counters.add("primary_compare", pairs=pairs_done, seconds=_time.perf_counter() - t0)

    if pod_live() is not None:
        # the elastic streaming stage lost pod member(s) and completed on
        # the survivors. The degradation carries into everything below:
        # checkpoint-store opens (SecondaryCheckpoint) route their
        # barriers over the live set (utils/ckptmeta.py), the secondary
        # engines clamp their mesh to LOCAL devices (engines._mesh_or_none
        # — a global mesh would dispatch a collective that waits on the
        # corpse forever), and the honest counters (dead_processes /
        # pod_epoch_bumps) ride into perf_counters.json
        # so a degraded run can never read as a clean measurement.
        logger.warning(
            "degraded pod: process(es) %s died during the primary stage; "
            "continuing the secondary loop on survivors %s (ownership "
            "epoch %d). Results are identical to a healthy run; restart "
            "the pod when convenient to restore capacity.",
            pod_dead(), pod_live(), pod_epoch(),
        )
    n_primary = int(primary.max()) if n else 0
    logger.info("primary clustering: %d clusters from %d genomes", n_primary, n)

    mdb = sparse_mdb
    if pdist is not None:
        with counters.span("mdb_build"):
            mdb = _mdb_from_dist(
                pdist, gs.names, kw["mdb_dense_limit"], kw["P_ani"],
                warn_dist=_warn_dist(kw),
            )
    if mdb is not None:
        with counters.span("tables_io", rows=len(mdb)) as io:
            io.note(bytes=wd.store_db(schemas.validate(mdb, "Mdb"), "Mdb"))
        _hold_for_evaluate(wd, mdb, "Mdb")

    clustering_files: dict[str, Any] = {
        "primary_linkage": plink,
        "primary_names": gs.names,
        "primary_dist": pdist if (pdist is not None and n <= kw["mdb_dense_limit"]) else None,
        "secondary": {},
    }

    ndb_parts: list[pairs.NdbColumns] = []
    tertiary_ndb: pd.DataFrame | None = None
    secondary_names: dict[str, str] = {}
    if kw["SkipSecondary"]:
        for i, g in enumerate(gs.names):
            secondary_names[g] = f"{primary[i]}_0"
    else:
        # a real span where two instants used to mark the stage: an open
        # with no close is still the crash evidence of a run that died
        # inside the ANI stage, and the stage's self time is host time
        # that no secondary/* phase names
        with counters.span("stage:secondary"):
            secondary_names, ndb_parts, clustering_files["secondary"] = _secondary_stage(
                gs, bdb, kw, ft_cfg, wd, snapshot, primary, n_primary
            )

    cdb = pd.DataFrame(
        {
            "genome": gs.names,
            "secondary_cluster": [secondary_names[g] for g in gs.names],
            "threshold": 1.0 - kw["S_ani"],
            "cluster_method": kw["clusterAlg"],
            "comparison_algorithm": kw["S_algorithm"],
            "primary_cluster": primary,
        }
    )

    if kw["run_tertiary_clustering"]:
        if kw["SkipSecondary"]:
            logger.warning(
                "--run_tertiary_clustering ignored: requires secondary clustering "
                "(remove --SkipSecondary)"
            )
        else:
            from drep_tpu.cluster.tertiary import run_tertiary_clustering

            cdb, tertiary_ndb = run_tertiary_clustering(gs, bdb, cdb, kw)

    # counted: CSV serialization of a 50k-scale Ndb is real wall that must
    # not hide in the uncounted remainder of a run; nor its assembly, the
    # job's one Ndb frame (no cluster's rows were a frame before this)
    with counters.stage("assembly_io"):
        ndb = pairs.assemble_ndb(ndb_parts) if ndb_parts else schemas.empty("Ndb")
        if tertiary_ndb is not None and len(tertiary_ndb):
            ndb = pd.concat([ndb, tertiary_ndb], ignore_index=True)
        wd.store_db(schemas.validate(ndb, "Ndb"), "Ndb")
        wd.store_db(schemas.validate(cdb, "Cdb"), "Cdb")

        cf_dir = wd.get_dir(os.path.join("data", "Clustering_files"))
        # atomic (utils/durableio.py): a SIGKILL mid-dump must not leave a
        # torn pickle that poisons a later resume's Clustering_files load
        from drep_tpu.utils.ckptmeta import atomic_write

        def _dump(tmp: str) -> None:
            # drep-lint: allow[durable-funnel] — write_fn body: `tmp` is the uuid tmp path durableio.atomic_write hands us
            with open(tmp, "wb") as f:
                # drep-lint: allow[durable-funnel] — dumps into the write_fn's tmp handle
                pickle.dump(clustering_files, f)

        atomic_write(os.path.join(cf_dir, "clustering.pickle"), _dump)
    _hold_for_evaluate(wd, ndb, "Ndb")

    with counters.span("tables_io"):
        wd.store_arguments("cluster", snapshot)
    logger.info(
        "clustering done: %d primary, %d secondary clusters",
        n_primary,
        cdb["secondary_cluster"].nunique(),
    )
    return cdb

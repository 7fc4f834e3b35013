"""`index classify`: membership queries answered from the index alone.

Read-only by contract: the queries are sketched in memory (the indexed
genomes are NEVER re-sketched — their sketches load from the store), the
K x N compare runs with no checkpoint store, the hypothetical admission
(the same dirty-component recluster `index update` would run) happens
entirely in memory, and nothing under the index directory is written —
the manifest generation is unchanged, asserted in tests. Because the
answer runs through the exact update machinery, a classify verdict IS
the assignment the genome would receive from `index update` (and, by
the pinned invariant, from a from-scratch rerun on the union).

Queries ride under internal ``query:``-prefixed names, so classifying a
FASTA whose basename is already indexed (e.g. re-checking an indexed
genome's own file) is a normal lookup, not a collision.

The resident-core API (ISSUE 11): the one-shot CLI and the `index
serve` daemon share ONE code path, split at the natural amortization
boundaries —

- :func:`load_resident_index` pays the expensive part once (manifest +
  shard reads); the returned index is what a daemon keeps resident.
- :func:`sketch_queries` turns FASTA paths into in-memory sketches
  under the index's pinned params (dup check, ``query:`` prefixing, the
  filter-length gate).
- :func:`classify_batch` answers any number of sketched queries from a
  resident index WITHOUT mutating it: every per-batch mutation happens
  on a scratch copy (fresh containers, shared immutable payloads), so a
  daemon classifies millions of batches off one load. ``joint=True``
  (the CLI's multi-genome semantics) classifies the batch as one
  hypothetical admission — queries may co-cluster with each other;
  ``joint=False`` (the daemon) answers each query INDEPENDENTLY, so a
  dynamically-coalesced batch returns verdicts identical to K separate
  one-shot classifies while still paying only ONE K x N rect compare.

Every verdict is stamped with the ``generation`` that produced it — the
hot-swap contract's anchor (a daemon that adopted generation G+1
mid-flight must say which generation answered each query).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

import numpy as np
import pandas as pd

from drep_tpu.errors import UserInputError
from drep_tpu.index.store import LoadedIndex, load_index
from drep_tpu.index.update import _admit_batch, _rect_edges, recluster
from drep_tpu.utils.logger import get_logger


def load_resident_index(
    index_loc: str, streaming: bool = True, resident_mb: int | None = None
) -> LoadedIndex:
    """Load the index once, read-only (``heal=False`` — classify refuses
    a rotted store instead of touching it). This is the load a daemon
    amortizes: everything after it is in-memory.

    A FEDERATED root (ISSUE 14) returns the STREAMING resident by
    default — ``federation.FederatedResident``, which holds only the
    union spine plus lazily-loaded hot partitions (LRU under
    ``resident_mb`` / ``DREP_TPU_SERVE_RESIDENT_MB``) and contains
    partition failure as PARTIAL verdicts instead of a failed load.
    ``streaming=False`` forces the full union assembly (the oracle path
    one-shot ``index classify`` keeps, and what the streaming verdicts
    are pinned identical to)."""
    from drep_tpu.index import meta as fedmeta

    if streaming and fedmeta.is_federated(index_loc):
        from drep_tpu.index.federation import FederatedResident

        # drep-lint: allow[reader-purity] — the streaming resident is read-only by construction: checked reads only (load_npz_checked/read_manifest), spine + lazy sketch loads, no durable-funnel writes; byte-for-byte pinned by test_fed_serve's tree-digest assertion
        return FederatedResident(index_loc, resident_mb=resident_mb)
    # drep-lint: allow[reader-purity] — heal=False pins the read-only load: corrupt shards REFUSE (UserInputError), never rewrite; the store's write/heal paths run only under `index update` (heal=True)
    return load_index(index_loc, heal=False)


def _scratch_index(idx: LoadedIndex) -> LoadedIndex:
    """A cheap classify-scratch copy of a resident index: fresh list
    containers (``_admit_batch`` extends them in place) sharing the
    per-genome payload arrays (immutable by contract — nothing in the
    classify path writes into a sketch row). Every other field is only
    ever REBOUND by the update machinery (``idx.edges = ...``,
    ``idx.primary = labels``), so sharing the current objects is safe:
    the resident index stays byte-identical through any number of
    batches (pinned by the serve tests)."""
    return LoadedIndex(
        location=idx.location, params=idx.params, generation=idx.generation,
        names=list(idx.names), locations=list(idx.locations),
        gdb=idx.gdb, admitted=idx.admitted,
        bottom=list(idx.bottom), scaled=list(idx.scaled),
        edges=idx.edges, primary=idx.primary, suffix=idx.suffix,
        score=idx.score, winners=idx.winners,
        sketch_shards=idx.sketch_shards, edge_shards=idx.edge_shards,
    )


@dataclass
class SketchedQueries:
    """One batch of queries, sketched and gated — the unit
    :func:`classify_batch` consumes. ``admitted`` rows carry the
    ``query:``-prefixed names; ``dropped`` holds the ready-made
    filtered-verdict dicts for queries below the index's filter
    length."""

    admitted: pd.DataFrame  # genome (query:-prefixed), location
    results: dict[str, dict]
    dropped: list[dict] = field(default_factory=list)

    @property
    def n(self) -> int:
        return len(self.admitted)


def sketch_queries(
    idx: LoadedIndex, genome_paths: list[str], processes: int = 1
) -> SketchedQueries:
    """Sketch the query FASTAs under the index's pinned params. Only the
    queries are ever sketched (the indexed genomes load from the store);
    duplicate basenames in one batch are refused (they would collide
    under the ``query:`` namespace — the daemon's batcher defers them to
    separate batches instead)."""
    from drep_tpu.ingest import sketch_paths

    p = idx.params
    if not genome_paths:
        return SketchedQueries(
            admitted=pd.DataFrame({"genome": [], "location": []}), results={}
        )
    basenames = [os.path.basename(g) for g in genome_paths]
    if len(set(basenames)) != len(basenames):
        raise UserInputError("duplicate genome basenames in the query list")
    bdb = pd.DataFrame(
        {
            "genome": [f"query:{b}" for b in basenames],
            "location": [os.path.abspath(g) for g in genome_paths],
        }
    )
    results = sketch_paths(
        bdb, int(p["kmer_size"]), int(p["sketch_size"]), int(p["scale"]),
        p["hash"], processes=processes,
    )
    min_len = int(p.get("filter_length", 0))
    admitted = bdb[
        [results[g]["length"] >= min_len for g in bdb["genome"]]
    ].reset_index(drop=True)
    dropped = []
    for g in sorted(set(bdb["genome"]) - set(admitted["genome"])):
        get_logger().warning(
            "classify: %s below the index's filter length %d", g, min_len
        )
        dropped.append(
            {
                "genome": g[len("query:"):],
                "filtered": True,
                "reason": f"below the index's filter length {min_len}",
                "generation": int(idx.generation),
            }
        )
    return SketchedQueries(admitted=admitted, results=results, dropped=dropped)


def _display(name: str) -> str:
    return name[len("query:"):] if name.startswith("query:") else name


def _assemble_verdicts(
    scratch: LoadedIndex,
    n_old: int,
    ii: np.ndarray,
    jj: np.ndarray,
    dd: np.ndarray,
    generation: int,
) -> list[dict]:
    """Verdict dicts for every query row (index >= n_old) of a
    reclustered scratch index. (ii, jj, dd) are the batch's NEW retained
    edges (jj >= n_old) — the nearest-indexed-genome lookup reads them
    directly."""
    winner_of = dict(zip(scratch.winners["cluster"], scratch.winners["genome"]))
    sec_names = scratch.secondary_names()
    # vectorized membership lookups: the per-query scans below must not
    # walk all N indexed genomes in interpreted Python on the serving path
    prim_old = scratch.primary[:n_old]
    sec_old = np.array(sec_names[:n_old], dtype=object)
    out: list[dict] = []
    for q in range(n_old, scratch.n):
        pc = int(scratch.primary[q])
        members = np.nonzero(prim_old == pc)[0].tolist()
        sec = sec_names[q]
        co = np.nonzero(sec_old == sec)[0].tolist()
        # nearest INDEXED genome among the query's retained edges
        touch = (jj == q) & (ii < n_old)
        nearest_i = nearest_d = None
        if touch.any():
            k = int(np.argmin(dd[touch]))
            nearest_i = int(ii[touch][k])
            nearest_d = float(dd[touch][k])
        winner = winner_of.get(sec)
        out.append(
            {
                "genome": _display(scratch.names[q]),
                "primary_cluster": pc,
                "secondary_cluster": sec,
                "novel_primary": not members,
                "novel_secondary": not co,
                "cluster_members": [scratch.names[i] for i in co],
                "winner": _display(winner) if winner is not None else None,
                "would_win": winner == scratch.names[q],
                "score": float(scratch.score[q]),
                "nearest": scratch.names[nearest_i] if nearest_i is not None else None,
                "nearest_dist": nearest_d,
                "generation": int(generation),
            }
        )
    return out


def classify_batch(
    resident: LoadedIndex,
    queries: SketchedQueries,
    processes: int = 1,
    prune_cfg: dict | None = None,
    joint: bool = True,
) -> list[dict]:
    """One verdict dict per admitted query, answered from `resident`
    WITHOUT mutating it (load once, classify many — the serving tier's
    contract). One K x N rectangular compare covers the whole batch
    whatever `joint` says; the modes differ only in host-side assembly:

    - ``joint=True``: the batch is one hypothetical admission — queries
      are clustered together with the index AND each other (the CLI's
      documented multi-genome semantics; query-query edges count).
    - ``joint=False``: each query is answered as if it were the only
      one (query-query edges are discarded; each verdict re-runs the
      dirty-component recluster with just its own query admitted) — a
      daemon's dynamically-coalesced batch answers exactly like K
      separate one-shot classifies, while the sketching and the rect
      compare are still paid once for the batch.

    ``prune_cfg`` ({"primary_prune": "lsh", "prune_bands": B,
    "prune_min_shared": F, "prune_join_chunk": C}) routes the compare
    through the SAME LSH candidate set `index update` consumes — recall
    1.0 at the index's retention bound, so the retained edges and
    therefore the VERDICTS are identical to the dense compare
    (property-tested). A pure execution knob on a read-only operation.

    A streaming federated resident (``federation.FederatedResident``,
    ISSUE 14) takes this same front door: the batch routes to candidate
    partitions by shared band codes, runs one per-partition rect compare
    each, and merges per-partition edges into the identical per-query
    verdicts — stamped ``partitions_consulted`` /
    ``partitions_unavailable`` (PARTIAL when a partition is quarantined).
    """
    from drep_tpu.index.federation import FederatedResident, classify_batch_federated

    if isinstance(resident, FederatedResident):
        # drep-lint: allow[reader-purity] — streaming federated classify is read-only: every rect compare runs storeless (no checkpoint_dir), residency loads are checked reads, verdict assembly is in-memory; byte-for-byte pinned by test_fed_serve's tree-digest assertion
        return classify_batch_federated(
            resident, queries, processes=processes, prune_cfg=prune_cfg,
            joint=joint,
        )
    if not queries.n:
        return []
    n_old = resident.n
    n_real = queries.n
    gen = int(resident.generation)
    scratch = _scratch_index(resident)
    admitted = queries.admitted
    if not joint and queries.n > 1:
        # SHAPE BUCKETING (the daemon's steady-state economics): the
        # rect compare's device shapes depend on the union size
        # N + K, so a daemon serving organically-sized batches would
        # pay an XLA compile (~100x one warm batch, measured) for
        # EVERY new K. Pad K to the next power of two with copies of
        # the first query under un-collidable names ("/" cannot appear
        # in a basename) — log-many shapes total, each compiled once
        # (and persisted by the XLA compile cache). Pad columns emit
        # pad-edges that the per-query jj == n_old + t selection below
        # never reads; verdicts are untouched (property-tested).
        k_pad = 1 << (queries.n - 1).bit_length()
        if k_pad > queries.n:
            first = admitted.iloc[0]
            pad_names = [f"query:/pad/{t}" for t in range(k_pad - queries.n)]
            pad = pd.DataFrame(
                {"genome": pad_names, "location": [first["location"]] * len(pad_names)}
            )
            admitted = pd.concat([admitted, pad], ignore_index=True)
            queries = SketchedQueries(
                admitted=admitted,
                results={
                    **queries.results,
                    **{p: queries.results[first["genome"]] for p in pad_names},
                },
                dropped=queries.dropped,
            )
    _admit_batch(scratch, admitted, queries.results, gen + 1)
    ii = jj = dd = None
    if not joint:
        # serve fast path: rect compare against the device-resident
        # sketch matrix (one upload per generation, not per batch); the
        # per-query jj == n_old + t selection below never reads the
        # query-query edges this path does not produce. None => classic.
        from drep_tpu.index.resident_device import rect_edges_device

        fast = rect_edges_device(resident, queries, n_old)
        if fast is not None:
            ii, jj, dd = fast
    from drep_tpu.utils.profiling import counters

    if ii is None:
        # in-memory rectangular compare: checkpoint_dir None => no writes
        with counters.span("index/rect_compare", genomes=scratch.n, min_col=n_old):
            # drep-lint: allow[reader-purity] — ckpt_dir=None gates the streaming engine storeless: no shard publishes, no heartbeat notes, no meta stamps (byte-for-byte pinned by test_index/test_serve digest assertions)
            ii, jj, dd, _pairs = _rect_edges(scratch, n_old, None, prune_cfg=prune_cfg)
    # canonical (ii, jj) order — the update path's convention: the
    # streaming federated path assembles the same edge SET from
    # per-partition compares, and identical ordering pins identical
    # tie-breaks (nearest-neighbor argmin, linkage merge order) so the
    # two paths' verdicts can be compared byte-for-byte
    with counters.span("index/rect_sort", edges=len(ii)):
        order = np.lexsort((jj, ii))
        ii, jj, dd = ii[order], jj[order], dd[order]
    if joint:
        scratch.edges = (
            np.concatenate([scratch.edges[0], ii]),
            np.concatenate([scratch.edges[1], jj]),
            np.concatenate([scratch.edges[2], dd]),
        )
        recluster(scratch, n_old, processes=processes)
        return _assemble_verdicts(scratch, n_old, ii, jj, dd, gen)
    out: list[dict] = []
    for t in range(n_real):
        # per-query scratch: admit ONLY this query, wire ONLY its edges
        # to INDEXED genomes (remapped to column n_old), recluster its
        # dirty components — byte-for-byte the one-shot single-query
        # answer, because pair distances are pack-independent
        sq = _scratch_index(resident)
        _admit_batch(
            sq, queries.admitted.iloc[[t]], queries.results, gen + 1
        )
        sel = (jj == n_old + t) & (ii < n_old)
        qii = ii[sel]
        qjj = np.full(int(sel.sum()), n_old, np.int64)
        qdd = dd[sel]
        sq.edges = (
            np.concatenate([sq.edges[0], qii]),
            np.concatenate([sq.edges[1], qjj]),
            np.concatenate([sq.edges[2], qdd]),
        )
        recluster(sq, n_old, processes=processes)
        out.extend(_assemble_verdicts(sq, n_old, qii, qjj, qdd, gen))
    return out


def index_classify(
    index_loc: str, genome_paths: list[str], processes: int = 1,
    primary_prune: str = "off", prune_bands: int = 0, prune_min_shared: int = 0,
    prune_join_chunk: int = 0,
) -> list[dict]:
    """One verdict dict per query: the primary/secondary cluster it would
    join, that cluster's winner (would the query itself win?), its nearest
    indexed genome by Mash distance, and whether it is novel (a cluster of
    its own). Queries are classified jointly when several are given — the
    single-query call is the pure membership lookup. The one-shot
    composition of the resident-core API: load + sketch + one joint
    batch (`index serve` holds the load and repeats the rest). A
    federated root is UNION-assembled here (``streaming=False``): the
    one-shot CLI is the oracle the streaming serve path is pinned
    against, and a batch tool has no residency budget to honor."""
    resident = load_resident_index(index_loc, streaming=False)
    queries = sketch_queries(resident, genome_paths, processes=processes)
    prune_cfg = {
        "primary_prune": primary_prune,
        "prune_bands": prune_bands,
        "prune_min_shared": prune_min_shared,
        "prune_join_chunk": prune_join_chunk,
    }
    out = classify_batch(
        resident, queries, processes=processes, prune_cfg=prune_cfg, joint=True
    )
    return out + queries.dropped

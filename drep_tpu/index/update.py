"""Incremental admission: K new genomes -> the next index generation.

The pinned invariant (ISSUE 6, property-tested): after any sequence of
``index update`` batches, the index's cluster labels (up to renumbering)
and winner sets are IDENTICAL to a from-scratch ``dereplicate`` over the
union set. The incremental algorithm earns that exactly, not
approximately, because every quantity the pipeline computes decomposes:

- **sketches** are per-genome (bottom-k / scaled of the genome's own
  hashes) — a new genome's sketch is what a union rerun would ingest.
- **Mash distances** are pair-local (the union-bottom-s estimator reads
  only the two rows), so the union's retained edge graph = stored edges
  + the K x N rectangular compare's new edges (computed through the SAME
  streaming tile executor, parallel/streaming.py ``min_col``).
- **primary clustering** (sparse UPGMA / connected components) never
  merges across connected components of the retained graph (a pair with
  no retained edge has average-bound keep > cutoff), so only components
  touched by a new genome ("dirty") can change — clean components keep
  their partition verbatim, dirty ones re-cluster through the same
  ops/linkage code the streaming primary runs.
- **secondary clustering + scoring** depend only on a primary cluster's
  member set (cluster-local ANI; row-local scores; centrality only to
  co-members) — recomputed for exactly the clusters whose member set
  changed, reused verbatim (member-set-keyed) for the rest: the secondary
  a cluster at a time through cluster/controller.py's
  ``secondary_for_cluster``, the scores and winners of ALL the changed
  clusters through ONE call of choose.py's ``score_and_pick`` (row-local,
  so the call over their union gives each cluster's own rows).

Crash story: the rectangular compare checkpoints per-stripe shards under
``<index>/pending/`` (the streaming store format), all new shards are
written under deterministic generation-stamped names, and the mutation
becomes visible only at the atomic manifest publish — a SIGKILL anywhere
(the ``index_update`` fault site makes the worst points deterministic)
leaves the previous generation intact and the rerun converges on the
uninterrupted result (chaos-tested via tools/chaos_matrix.py --index).
"""

from __future__ import annotations

import time

import numpy as np
import pandas as pd

from drep_tpu.errors import UserInputError
from drep_tpu.index.store import IndexStore, LoadedIndex, build_manifest, load_index
from drep_tpu.utils.logger import get_logger

_STAT_COLS = ("length", "N50", "contigs", "n_kmers")
# the Ndb rows one score_and_pick call may gather: past it the changed
# clusters gathered so far go through a call of their own, cut at cluster
# boundaries — the frames' peak is this or the largest cluster, whatever the
# number of clusters an `index build -g` or a rebuild recomputes at once
SCORE_ROWS_MAX = 4_000_000
# all compute_centrality reads of an Ndb (the index keeps no pair table)
_SCORE_NDB_COLS = ("querry", "reference", "ani")


def _genome_sketches(idx: LoadedIndex):
    """The union set as the GenomeSketches the secondary engines consume."""
    from drep_tpu.ingest import GenomeSketches

    p = idx.params
    return GenomeSketches(
        names=idx.names, gdb=idx.gdb, bottom=idx.bottom, scaled=idx.scaled,
        k=int(p["kmer_size"]), sketch_size=int(p["sketch_size"]),
        scale=int(p["scale"]),
    )


def _retention(params: dict) -> tuple[float, float]:
    from drep_tpu.parallel.streaming import retention_bound

    cutoff = 1.0 - float(params["P_ani"])
    return cutoff, retention_bound(
        cutoff, float(params["warn_dist"]), params["clusterAlg"]
    )


def _rect_edges(
    idx: LoadedIndex, n_old: int, checkpoint_dir: str | None, prune_cfg: dict | None = None
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """New retained edges (jj >= n_old) of the union set, through the
    streaming tile executor's rectangular schedule.

    `prune_cfg` ({"primary_prune": "lsh", "prune_bands": B,
    "prune_min_shared": F}) feeds the SAME LSH candidate set the
    streaming primary uses into the rectangular compare — K x N becomes
    K x bucket_occupancy (ROADMAP service-mode follow-on (a)): the
    candidate build runs over the union pack at the index's own
    retention bound, restricted to pairs reaching the new-genome tail
    (jj >= n_old), so recall 1.0 and the admitted edge set is identical
    to the unpruned compare's."""
    from drep_tpu.ops.minhash import pack_sketches
    from drep_tpu.parallel.streaming import streaming_mash_edges
    from drep_tpu.utils.profiling import counters

    p = idx.params
    _, keep = _retention(p)
    # the union's pack: every genome's hashes ranked again, whatever the batch
    with counters.span("index/rect_pack", genomes=idx.n):
        packed = pack_sketches(idx.bottom, idx.names, int(p["sketch_size"]))
    prune = None
    if prune_cfg and prune_cfg.get("primary_prune", "off") == "lsh":
        from drep_tpu.ops.lsh import build_candidates

        prune = build_candidates(
            packed, keep=keep, k=int(p["kmer_size"]),
            bands=int(prune_cfg.get("prune_bands", 0)),
            min_shared=int(prune_cfg.get("prune_min_shared", 0)),
            min_col=n_old,
            join_chunk=int(prune_cfg.get("prune_join_chunk", 0)),
        )
    ii, jj, dd, pairs = streaming_mash_edges(
        packed, int(p["kmer_size"]), keep,
        block=int(p["streaming_block"]),
        checkpoint_dir=checkpoint_dir, min_col=n_old, prune=prune,
    )
    sel = jj >= n_old  # boundary tiles emit a few old-old pairs: already stored
    return ii[sel], jj[sel], dd[sel], pairs


def rect_compare(
    idx: LoadedIndex, n_old: int, checkpoint_dir: str | None, prune_cfg: dict | None = None
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """:func:`_rect_edges` as the verbs run it: inside the stage
    `index_rect_compare` and the span `index/rect_compare`, its pairs, tiles
    and new edges booked in the record's `index`, the edges in canonical
    (ii, jj) order."""
    from drep_tpu.utils.profiling import counters

    def tiles_done() -> int:
        walked = counters.stages.get("primary_compare")
        return walked.tiles_computed if walked else 0

    tiles_before = tiles_done()
    with counters.stage("index_rect_compare"), counters.span(
        "index/rect_compare", genomes=idx.n, min_col=n_old
    ):
        ii, jj, dd, pairs = _rect_edges(idx, n_old, checkpoint_dir, prune_cfg=prune_cfg)
    counters.stages["index_rect_compare"].pairs += pairs
    counters.add_index(pairs_compared=pairs, tiles=tiles_done() - tiles_before, new_edges=len(ii))
    with counters.span("index/rect_sort", edges=len(ii)):
        order = np.lexsort((jj, ii))
        ii, jj, dd = ii[order], jj[order], dd[order]
    return ii, jj, dd, pairs


def _primary_partition(idx: LoadedIndex, n_old: int) -> tuple[np.ndarray, list[list[int]], int]:
    """The union primary partition, re-clustering ONLY dirty components.

    Returns (labels 1..C renumbered by first appearance — exactly the
    from-scratch numbering, the member lists per label, and the number of
    components actually re-clustered)."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components as _cc

    n = idx.n
    ii, jj, dd = idx.edges
    cutoff, keep = _retention(idx.params)
    graph = coo_matrix((np.ones(len(ii), np.int8), (ii, jj)), shape=(n, n))
    _, comp = _cc(graph, directed=False)
    dirty = np.zeros(int(comp.max()) + 1 if n else 0, dtype=bool)
    if n_old < n:
        dirty[np.unique(comp[n_old:])] = True
    if idx.state_missing:
        dirty[:] = True  # rotted state: every component re-clusters

    group_of = np.full(n, -1, np.int64)
    next_key = 0
    # clean components: the stored partition restricted to them is the
    # union answer verbatim — group by the OLD primary label
    clean_nodes = np.nonzero(~dirty[comp])[0] if n else np.empty(0, np.int64)
    if len(clean_nodes):
        old_labels = idx.primary[clean_nodes]
        uniq = np.unique(old_labels)
        remap = {int(l): next_key + i for i, l in enumerate(uniq)}
        group_of[clean_nodes] = [remap[int(l)] for l in old_labels]
        next_key += len(uniq)

    reclustered = 0
    edge_comp = comp[ii] if len(ii) else np.empty(0, comp.dtype)
    for c in np.nonzero(dirty)[0]:
        members = np.nonzero(comp == c)[0]
        reclustered += 1
        if len(members) == 1:
            group_of[members[0]] = next_key
            next_key += 1
            continue
        local = np.full(n, -1, np.int64)
        local[members] = np.arange(len(members))
        sel = edge_comp == c
        li, lj, ld = local[ii[sel]], local[jj[sel]], dd[sel]
        if idx.params["clusterAlg"] == "single":
            from drep_tpu.parallel.streaming import connected_components

            inc = ld <= cutoff
            sub = connected_components(len(members), li[inc], lj[inc])
        else:
            from drep_tpu.ops.linkage import sparse_average_linkage

            sub, approx = sparse_average_linkage(
                len(members), li, lj, ld, cutoff, keep
            )
            if approx:
                get_logger().warning(
                    "index update: %d accepted merges in a re-clustered "
                    "component involved pairs beyond the %.3f retention "
                    "bound — same caveat as the streaming primary",
                    approx, keep,
                )
        group_of[members] = next_key + sub - 1  # sub is 1-based
        next_key += int(sub.max())

    # renumber by first appearance in genome order — the from-scratch rule
    labels = np.zeros(n, np.int64)
    members_of: dict[int, list[int]] = {}
    order: list[int] = []
    for i in range(n):
        g = int(group_of[i])
        if g not in members_of:
            members_of[g] = []
            order.append(g)
        members_of[g].append(i)
    groups: list[list[int]] = []
    for new_id, g in enumerate(order, start=1):
        labels[members_of[g]] = new_id
        groups.append(members_of[g])
    return labels, groups, reclustered


def _score_column(ndb, c: str) -> np.ndarray:
    """Column `c` of one cluster's Ndb rows (pairs.NdbColumns) for the frame
    score_and_pick reads: a name column as objects that SHARE the cluster's
    own name strings — `ndb.column(c)` is a `<U` array, of which a frame
    makes a new str (and the group-bys a new hash) a row."""
    from drep_tpu.cluster import pairs

    if ndb.names is None or c not in pairs.NAME_COLUMNS:
        return ndb.column(c)
    return ndb.names.astype(object)[ndb.cols[c]]


class _ScoreBatch:
    """The recomputed clusters gathered for ONE call of score_and_pick, the
    choose core the batch pipeline runs over all its clusters: scores are
    row-local (own stats + centrality to co-members), so the call over a
    union of clusters gives exactly the rows and winners a call a cluster
    would, at one set of frames where that built one a cluster. A flush
    fills `score[members]` and appends the clusters' winners (pick_winners:
    score desc, genome asc) to `win_rows`."""

    def __init__(self, idx: LoadedIndex, score: np.ndarray, win_rows: list) -> None:
        self.idx, self.score, self.win_rows = idx, score, win_rows
        self.calls = 0
        self._clear()

    def _clear(self) -> None:
        self.members: list[int] = []
        self.sec_names: list[str] = []
        self.ndbs: list = []  # pairs.NdbColumns, one a cluster of two or more
        self.clusters = self.rows = 0

    def add(self, pc: int, members: list[int], labels, ndb=None) -> None:
        """One cluster: its members, their secondary labels and its Ndb rows
        (pairs.NdbColumns; a singleton has one Cdb row and none). What was
        gathered is scored first where these rows would pass SCORE_ROWS_MAX."""
        if ndb is not None:
            if self.rows and self.rows + len(ndb) > SCORE_ROWS_MAX:
                self.flush()
            self.ndbs.append(ndb)
            self.rows += len(ndb)
        self.members.extend(members)
        self.sec_names.extend(f"{pc}_{int(l)}" for l in labels)
        self.clusters += 1

    def flush(self) -> None:
        if not self.members:
            return
        from drep_tpu.choose import score_and_pick
        from drep_tpu.utils.profiling import counters

        idx = self.idx
        self.calls += 1
        with counters.span(
            "index/score", members=len(self.members), clusters=self.clusters, pairs=self.rows
        ):
            cdb = pd.DataFrame(
                {"genome": [idx.names[i] for i in self.members], "secondary_cluster": self.sec_names}
            )
            stats = idx.gdb.iloc[self.members][["genome", "length", "N50"]]
            ndb = pd.DataFrame(
                {
                    c: np.concatenate([_score_column(p, c) for p in self.ndbs])
                    if self.ndbs else np.empty(0)
                    for c in _SCORE_NDB_COLS
                }
            )
            sdb_full, wdb = score_and_pick(
                cdb, stats, ndb, None, S_ani=idx.params["S_ani"], **idx.params["weights"]
            )
            # the left merge on unique names keeps Cdb's row order
            self.score[self.members] = sdb_full["score"].to_numpy(np.float64)
            self.win_rows.extend(zip(wdb["cluster"], wdb["genome"], wdb["score"]))
        self._clear()


def recluster(idx: LoadedIndex, n_old: int, processes: int = 1) -> dict:
    """Recompute the index's derived state after `idx` gained genomes
    beyond `n_old` (sketches + edges already extended in memory). Mutates
    idx.primary/suffix/score/winners; returns an honest summary.

    ``idx.frozen_rows`` (set by the streaming federated serving path,
    ISSUE 14) marks genomes whose sketch payloads are UNAVAILABLE
    (quarantined partitions): they keep their old primary label (the
    clean-cluster structure and renumbering are untouched), carry their
    old suffix/score verbatim when their cluster is reused whole, and
    when a recompute would touch them (their cluster was split by the
    exclusion) they are carried with sentinel suffix 0 + old score while
    only the AVAILABLE members re-cluster — never routed into a
    secondary engine their sketches cannot feed."""
    from drep_tpu.cluster.controller import secondary_for_cluster

    t0 = time.perf_counter()
    old_primary = idx.primary
    old_suffix = idx.suffix
    old_score = idx.score
    frozen: set[int] = set(
        int(i) for i in getattr(idx, "frozen_rows", ())
    )
    # member-set-keyed reuse: any union primary cluster whose member set
    # equals an old one has IDENTICAL secondary results and scores (they
    # depend only on the members) — old indices are stable, so frozensets
    # compare directly
    old_groups: dict[frozenset, bool] = {}
    if n_old and not idx.state_missing:
        by_label: dict[int, list[int]] = {}
        for i in range(n_old):
            by_label.setdefault(int(old_primary[i]), []).append(i)
        old_groups = {frozenset(v): True for v in by_label.values()}

    from drep_tpu.utils.profiling import counters

    with counters.span("index/partition", genomes=idx.n, edges=len(idx.edges[0])):
        labels, groups, reclustered_comps = _primary_partition(idx, n_old)
    n = idx.n
    suffix = np.zeros(n, np.int64)
    score = np.zeros(n, np.float64)
    gs = _genome_sketches(idx)
    bdb = pd.DataFrame({"genome": idx.names, "location": idx.locations})
    kw = {
        "S_algorithm": idx.params["S_algorithm"],
        "S_ani": idx.params["S_ani"],
        "cov_thresh": idx.params["cov_thresh"],
        "clusterAlg": idx.params["clusterAlg"],
        "processes": processes,
        "mesh_shape": None,
    }
    # incremental verdict assembly (ISSUE 13 satellite): only a touched
    # cluster's winner can change, so the winner table is SPLICED — reused
    # clusters keep their old winner row verbatim (identical member sets
    # have identical scores), recomputed clusters take theirs from the one
    # score_and_pick call that scores them (ISSUE 51) — instead of
    # re-running choose.pick_winners + the score pandas path over all
    # N per batch (the serving tier's per-query recluster floor). `_pick`,
    # a reused cluster's fallback, is pick_winners' argmax/tie rule exactly
    # (score desc, genome asc; output ordered by cluster name ascending),
    # oracle-pinned in tests.
    reused = recomputed = members_recomputed = secondary_calls = singletons_scored = 0
    win_rows: list[tuple[str, str, float]] = []  # (cluster, genome, score)
    old_win: dict[str, tuple[str, float]] = {}
    if old_groups:
        for row in idx.winners.itertuples():
            old_win[str(row.cluster)] = (str(row.genome), float(row.score))

    def _pick(cands: list[tuple[str, float]]) -> tuple[str, float]:
        return min(cands, key=lambda t: (-t[1], t[0]))

    batch = _ScoreBatch(idx, score, win_rows)

    for pc, members in enumerate(groups, start=1):
        fs = frozenset(members)
        if fs in old_groups:
            suffix[members] = old_suffix[members]
            score[members] = old_score[members]
            reused += 1
            by_s: dict[int, list[int]] = {}
            for i in members:
                by_s.setdefault(int(old_suffix[i]), []).append(i)
            for s_val, mem in sorted(by_s.items()):
                old_name = f"{int(old_primary[mem[0]])}_{s_val}"
                won = old_win.get(old_name) or _pick(
                    [(idx.names[i], float(old_score[i])) for i in mem]
                )
                win_rows.append((f"{pc}_{s_val}", won[0], won[1]))
            continue
        recomputed += 1
        members_recomputed += len(members)
        if frozen:
            held = [i for i in members if i in frozen]
            if held:
                # unavailable members ride along with sentinel suffix 0
                # (never a real secondary) and their old score; only the
                # available remainder re-clusters below
                for i in held:
                    suffix[i] = 0
                    score[i] = old_score[i] if i < len(old_score) else 0.0
                members = [i for i in members if i not in frozen]
                if not members:
                    continue  # whole cluster unavailable: no winner row
        if len(members) == 1:
            suffix[members[0]] = 1  # the pipeline's singleton convention ("pc_1")
            singletons_scored += 1
            batch.add(pc, members, (1,))
            continue
        secondary_calls += 1
        with counters.span("index/secondary", members=len(members)):
            ndb, labs, _link = secondary_for_cluster(gs, bdb, list(members), pc, kw)
        suffix[members] = labs
        batch.add(pc, members, labs, ndb)
    batch.flush()

    idx.primary = labels
    idx.suffix = suffix
    idx.score = score
    win_rows.sort(key=lambda r: r[0])  # pick_winners' output order
    idx.winners = pd.DataFrame(
        {
            "cluster": [r[0] for r in win_rows],
            "genome": [r[1] for r in win_rows],
            "score": np.array([r[2] for r in win_rows], np.float64),
        }
    )
    counters.add_index(
        components_reclustered=reclustered_comps, clusters_reused=reused,
        clusters_recomputed=recomputed, members_recomputed=members_recomputed,
        secondary_calls=secondary_calls, singletons_scored=singletons_scored,
        score_calls=batch.calls,
    )
    return {
        "primary_clusters": int(labels.max()) if n else 0,
        "secondary_clusters": len(win_rows),
        "components_reclustered": reclustered_comps,
        "clusters_reused": reused,
        "clusters_recomputed": recomputed,
        "seconds": round(time.perf_counter() - t0, 2),
    }


def _admit_batch(
    idx: LoadedIndex, batch: pd.DataFrame, results: dict[str, dict], gen_new: int
) -> int:
    """Extend idx in memory with the sketched batch; returns n_old."""
    n_old = idx.n
    names_new = list(batch["genome"])
    idx.names.extend(names_new)
    idx.locations.extend(batch["location"])
    rows = pd.DataFrame(
        {
            "genome": names_new,
            **{c: [results[g][c] for g in names_new] for c in _STAT_COLS},
        }
    )
    idx.gdb = pd.concat([idx.gdb, rows], ignore_index=True)
    idx.admitted = np.concatenate(
        [idx.admitted, np.full(len(names_new), gen_new, np.int64)]
    )
    idx.bottom.extend(results[g]["bottom"] for g in names_new)
    idx.scaled.extend(results[g]["scaled"] for g in names_new)
    return n_old


def sketch_batch(idx: LoadedIndex, genome_paths: list[str], processes: int = 1):
    """make_bdb + duplicate check + length filter + sketch — the index's
    ingest front door, shared by update and classify."""
    from drep_tpu.ingest import make_bdb, sketch_paths

    bdb = make_bdb(genome_paths)
    dup = sorted(set(bdb["genome"]) & set(idx.names))
    if dup:
        raise UserInputError(
            f"{len(dup)} genome basename(s) already indexed: {dup[:5]} — "
            f"the index keys genomes by basename; rename the files or "
            f"rebuild if they are replacements"
        )
    p = idx.params
    results = sketch_paths(
        bdb, int(p["kmer_size"]), int(p["sketch_size"]), int(p["scale"]),
        p["hash"], processes=processes,
    )
    min_len = int(p.get("filter_length", 0))
    dropped = [g for g in bdb["genome"] if results[g]["length"] < min_len]
    if dropped:
        get_logger().warning(
            "index: %d genome(s) below the index's filter length %d — "
            "not admitted (same rule the batch pipeline's filter stage "
            "applies): %s", len(dropped), min_len, dropped[:5],
        )
        bdb = bdb[~bdb["genome"].isin(dropped)].reset_index(drop=True)
    return bdb, results


def publish_generation(
    store: IndexStore,
    idx: LoadedIndex,
    gen_new: int,
    n_old: int,
    new_edges: tuple[np.ndarray, np.ndarray, np.ndarray],
) -> None:
    """Persist one admitted batch as generation `gen_new`: shards first
    (deterministic names + content — a rerun after a kill rewrites them
    identically), the manifest last (THE commit point), cleanup after.
    Shared by `index update` and the fresh `index build` (whose batch is
    the whole initial set at generation 0)."""
    from drep_tpu.utils import faults
    from drep_tpu.utils.profiling import counters

    with counters.span("index/publish", generation=gen_new, genomes=idx.n - n_old):
        store.ensure_dirs()
        sk_rel = store.sketch_shard_name(gen_new)
        ed_rel = store.edge_shard_name(gen_new)
        st_rel = store.state_name(gen_new)
        with counters.span("index/publish_sketch"):
            store.write_sketch_shard(
                sk_rel, idx.names[n_old:], idx.locations[n_old:], idx.gdb.iloc[n_old:],
                idx.bottom[n_old:], idx.scaled[n_old:], gen_new,
            )
        ii, jj, dd = new_edges
        with counters.span("index/publish_edges", edges=len(ii)):
            store.write_edge_shard(ed_rel, ii, jj, dd)
        with counters.span("index/publish_state"):
            store.write_state(st_rel, idx)
        idx.generation = gen_new
        idx.sketch_shards = idx.sketch_shards + [
            {"file": sk_rel, "lo": n_old, "hi": idx.n, "generation": gen_new}
        ]
        idx.edge_shards = idx.edge_shards + [
            {"file": ed_rel, "lo": n_old, "hi": idx.n, "generation": gen_new}
        ]
        faults.fire("index_update")  # pre-publish point (skip=1 targets it)
        with counters.span("index/publish_manifest"):
            store.publish_manifest(build_manifest(idx, st_rel))
            store.gc_states(st_rel)
    counters.add_index(admitted=idx.n - n_old, generation=gen_new, n_old=n_old)


def materialize_generation0(
    store: IndexStore, params: dict, batch: pd.DataFrame,
    results: dict[str, dict], processes: int = 1,
) -> dict:
    """Generation 0 of a NEW store from pre-sketched genomes and PINNED
    params — the federated partition-materialization core (ISSUE 14
    satellite): the ordinary bootstrap build resolves params from CLI
    kwargs, but a federation partition must inherit the meta's params
    verbatim (build-time and update-time numerics can never drift), and
    under ``--fed_pods`` the pinned params cannot ride the CLI — they
    arrive through the params-file handoff instead."""
    from drep_tpu.index.store import empty_index
    from drep_tpu.utils.profiling import counters

    if not len(batch):
        raise UserInputError(
            f"partition {store.location}: no routed genome survived the "
            f"length filter — nothing to materialize"
        )
    idx = empty_index(dict(params), location=store.location)
    with counters.span("index/admit", genomes=len(batch)):
        _admit_batch(idx, batch, results, 0)
    ii, jj, dd, pairs = rect_compare(idx, 0, store.pending_dir(0))
    idx.edges = (ii, jj, dd)
    summary = recluster(idx, 0, processes=processes)
    publish_generation(store, idx, 0, 0, idx.edges)
    summary.update(
        {
            "admitted": idx.n, "n_genomes": idx.n, "generation": 0,
            "new_edges": int(len(ii)), "pairs_compared": int(pairs),
            "healed": [],
        }
    )
    return summary


def index_update(
    index_loc: str, genome_paths: list[str] | None, processes: int = 1,
    primary_prune: str = "off", prune_bands: int = 0, prune_min_shared: int = 0,
    prune_join_chunk: int = 0, fed_pods: int | None = None,
    params_file: str | None = None,
    presketched: tuple[pd.DataFrame, dict] | None = None,
) -> dict:
    """`index update`: admit K new genomes (sketch K, compare K x N,
    re-cluster dirty components, re-score touched clusters) and publish
    the next generation. With no genomes this is a pure HEAL pass:
    corrupt/missing shards repair and the generation stays put.

    A FEDERATED root (index/federation.py) takes this same front door:
    the batch routes to range partitions by sketch-derived code, each
    dirty partition updates as an independent unit (``fed_pods`` > 0
    runs them as concurrent subprocess pods), and the federation
    generation publishes through the meta-manifest.

    `primary_prune="lsh"` routes the rect compare through the LSH
    candidate set (see _rect_edges) — a per-invocation execution knob,
    never pinned in the manifest, because the admitted edges are
    identical either way (recall 1.0 at the retention bound).

    ``params_file`` (ISSUE 14 satellite, the pods-can't-ride-the-CLI
    fix): a sketches+params handoff written by a federated router
    (``federation.write_params_handoff``). The routed batch's sketches
    ride it — the pod never re-sketches what the router already
    sketched — and a store that does not exist yet MATERIALIZES
    generation 0 with the handoff's pinned params, so even a partition's
    first batch parallelizes under ``--fed_pods``. ``presketched`` is
    the in-process equivalent (the router passes its (batch, results)
    directly)."""
    from drep_tpu.index import meta as fedmeta
    from drep_tpu.utils import faults
    from drep_tpu.utils.profiling import counters

    if fedmeta.is_federated(index_loc):
        from drep_tpu.index.federation import fed_update

        if params_file or presketched:
            raise UserInputError(
                "--params_file targets ONE partition store (the router "
                "writes it); the federation root takes plain -g genomes"
            )
        return fed_update(
            index_loc, genome_paths, processes=processes, fed_pods=fed_pods,
            primary_prune=primary_prune, prune_bands=prune_bands,
            prune_min_shared=prune_min_shared, prune_join_chunk=prune_join_chunk,
        )
    logger = get_logger()
    store = IndexStore(index_loc)
    handoff_params = None
    if params_file:
        from drep_tpu.index.federation import read_params_handoff

        with counters.span("index/handoff_read"):
            handoff = read_params_handoff(params_file, workers=processes)
        handoff_params = handoff["params"]
        presketched = (handoff["batch"], handoff["results"])
        if not store.exists():
            # partition materialization in a pod: generation 0 under the
            # handoff's PINNED params (the same `index_update` fault
            # site as the ordinary path fires inside publish_generation)
            return materialize_generation0(
                store, handoff_params, *presketched, processes=processes
            )
    idx = load_index(index_loc, heal=True, workers=processes)
    if handoff_params is not None and dict(idx.params) != dict(handoff_params):
        raise UserInputError(
            f"params handoff {params_file} pins different params than the "
            f"store at {index_loc} — the handoff belongs to a different "
            f"federation (or generation); refuse rather than drift numerics"
        )
    faults.fire("index_update")  # batch admission point (chaos)
    gen_new = idx.generation + 1

    batch = results = None
    if presketched is not None:
        batch, results = presketched
        dup = sorted(set(batch["genome"]) & set(idx.names))
        if dup:
            raise UserInputError(
                f"{len(dup)} handoff genome basename(s) already indexed: "
                f"{dup[:5]} — the router routed a batch this store already "
                f"admitted (resume the interrupted update instead)"
            )
    elif genome_paths:
        with counters.span("index/sketch", genomes=len(genome_paths)):
            batch, results = sketch_batch(idx, genome_paths, processes=processes)
    if batch is None or not len(batch):
        # heal-only pass: rotted state recomputes (all components dirty),
        # healed shards were already rewritten by load_index — the
        # generation does NOT bump (nothing was admitted)
        summary = {"admitted": 0, "generation": idx.generation, "healed": idx.healed}
        if idx.state_missing:
            summary.update(recluster(idx, idx.n, processes=processes))
            store.write_state(store.state_name(idx.generation), idx)
            logger.warning("index: state payload healed via full recompute")
        if idx.healed:
            logger.info("index heal pass: repaired %s", idx.healed)
        return summary

    with counters.span("index/admit", genomes=len(batch)):
        n_old = _admit_batch(idx, batch, results, gen_new)
    prune_cfg = {
        "primary_prune": primary_prune,
        "prune_bands": prune_bands,
        "prune_min_shared": prune_min_shared,
        "prune_join_chunk": prune_join_chunk,
    }
    ii, jj, dd, pairs = rect_compare(idx, n_old, store.pending_dir(gen_new), prune_cfg=prune_cfg)
    idx.edges = (
        np.concatenate([idx.edges[0], ii]),
        np.concatenate([idx.edges[1], jj]),
        np.concatenate([idx.edges[2], dd]),
    )
    summary = recluster(idx, n_old, processes=processes)

    publish_generation(store, idx, gen_new, n_old, (ii, jj, dd))
    summary.update(
        {
            "admitted": idx.n - n_old,
            "n_genomes": idx.n,
            "generation": gen_new,
            "new_edges": int(len(ii)),
            "pairs_compared": int(pairs),
            "healed": idx.healed,
        }
    )
    if primary_prune == "lsh":
        # pruning honesty rides into the update summary: what fraction of
        # the rect schedule the candidate bitmap removed (the gauge the
        # streaming walk just set), alongside the pairs actually compared
        summary["primary_prune"] = "lsh"
        summary["skip_fraction"] = counters.gauges.get("skip_fraction", 0.0)
    logger.info(
        "index update: +%d genomes -> generation %d (%d genomes, %d primary / "
        "%d secondary clusters; %d cluster(s) recomputed, %d reused)",
        summary["admitted"], gen_new, idx.n, summary["primary_clusters"],
        summary["secondary_clusters"], summary["clusters_recomputed"],
        summary["clusters_reused"],
    )
    return summary

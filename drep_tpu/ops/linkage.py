"""Hierarchical clustering on distance matrices.

Reference parity: drep/d_cluster/utils.py::cluster_hierarchical — pivot pair
table -> square matrix -> scipy linkage(method=clusterAlg) -> fcluster(
t=1-threshold, criterion='distance') (SURVEY.md §2; reference mount empty).

Two engines:
- ``scipy`` (host): exact reference semantics for every linkage method
  (average is the reference default). Fine through ~10k genomes. The dense
  primary takes only its flat clusters, from the components of the graph of
  pairs under the cutoff (:func:`cluster_by_components`): scipy runs inside
  the components that are not all under it, and on nothing else.
- ``device`` (jit): single-linkage flat clusters at a cutoff == connected
  components of the thresholded distance graph, computed as min-label
  propagation (a few O(N^2) matrix ops per sweep — XLA/VPU friendly, no
  data-dependent shapes). Used by the large-N / on-device paths where
  average linkage's sequential merges don't map to the hardware.

Cluster labels are renumbered 1..C by first appearance in genome order,
deterministically, for both engines (so goldens are stable).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import scipy.cluster.hierarchy as sch
import scipy.spatial.distance as ssd


def _renumber_first_appearance(labels: np.ndarray) -> np.ndarray:
    """Map arbitrary labels -> 1..C ordered by first appearance."""
    out = np.zeros(len(labels), dtype=np.int64)
    mapping: dict[int, int] = {}
    for i, lab in enumerate(labels):
        key = int(lab)
        if key not in mapping:
            mapping[key] = len(mapping) + 1
        out[i] = mapping[key]
    return out


def cluster_hierarchical(
    dist: np.ndarray,
    cutoff: float,
    method: str = "average",
) -> tuple[np.ndarray, np.ndarray]:
    """Flat clusters of a square distance matrix at cophenetic cutoff.

    Returns (labels 1..C int64 by first appearance, scipy linkage matrix).
    """
    dist = np.asarray(dist, dtype=np.float64)
    n = dist.shape[0]
    if n == 1:
        return np.ones(1, dtype=np.int64), np.empty((0, 4))
    dist = np.maximum(dist, dist.T)  # enforce symmetry for squareform
    np.fill_diagonal(dist, 0.0)
    condensed = ssd.squareform(dist, checks=False)
    link = sch.linkage(condensed, method=method)
    labels = sch.fcluster(link, t=cutoff, criterion="distance")
    return _renumber_first_appearance(labels), link


# linkage methods whose distance between two clusters is a convex combination
# (or the minimum, or the maximum) of the pair distances across them: no two
# clusters merge at or under a cutoff unless some pair across them is, and a
# set whose every pair is under the cutoff merges wholly under it
COMPONENT_METHODS = frozenset({"average", "single", "complete", "weighted"})


def _cutoff_in(dtype: np.dtype, cutoff: float):
    """The largest value of `dtype` that is <= `cutoff`: `d <= that` in the
    matrix's own dtype decides what `float64(d) <= cutoff` decides (NumPy
    would round a Python float to float32 first, and 1 - 0.9 rounds UP)."""
    c = dtype.type(cutoff)
    return np.nextafter(c, dtype.type(-np.inf)) if float(c) > cutoff else c


def _components(adj: np.ndarray) -> tuple[np.ndarray, int]:
    """(component id of every node, number of components) of a symmetric
    boolean adjacency, breadth first: every node's row is read once and
    nothing but the ids is allocated, however dense the graph."""
    m = adj.shape[0]
    comp = np.full(m, -1, dtype=np.int64)
    n_comp = 0
    for start in range(m):
        if comp[start] >= 0:
            continue
        comp[start] = n_comp
        frontier = np.array([start])
        while frontier.size:
            reach = adj[frontier].any(axis=0)
            reach &= comp < 0
            frontier = np.flatnonzero(reach)
            comp[frontier] = n_comp
        n_comp += 1
    return comp, n_comp


def cluster_by_components(
    dist: np.ndarray,
    cutoff: float,
    method: str = "average",
) -> tuple[np.ndarray, dict[str, int]]:
    """The flat clusters :func:`cluster_hierarchical` cuts at `cutoff`, without
    the tree above the cutoff: (labels 1..C by first appearance, what was done).

    For the :data:`COMPONENT_METHODS` no merge at or under the cutoff joins two
    clusters with every pair across them over it, so every flat cluster lies
    inside one connected component of the graph `max(d[i,j], d[j,i]) <=
    cutoff` (`cluster_hierarchical` symmetrises by the maximum). Singletons of
    that graph are clusters; a component whose every pair is under the cutoff
    is one cluster whatever the merge order; any other component goes through
    `cluster_hierarchical` on its own submatrix, members in ascending order. A
    matrix that is one loose component costs the whole linkage plus the pass.
    `ward` (and anything else) takes `cluster_hierarchical` on the whole matrix.

    The partition is `cluster_hierarchical`'s up to the order of exactly tied
    merges inside a loose component (scipy's nearest-neighbour chain starts
    elsewhere on a submatrix), and up to the last bits of scipy's running means
    where a distance lies within rounding of the cutoff (PARITY.md).

    The second value counts `genomes`, `components`, `singletons`, `cliques`
    (components of two or more settled with no linkage call), `linkage_calls`,
    `rows_linked` (genomes that went through scipy) and `largest` (component).
    """
    dist = np.asarray(dist)
    if not np.issubdtype(dist.dtype, np.floating):
        dist = dist.astype(np.float64)
    n = dist.shape[0]
    if method not in COMPONENT_METHODS:  # the whole matrix as one loose component
        labels, _ = cluster_hierarchical(dist, cutoff, method=method)
        return labels, {"genomes": n, "components": 1, "singletons": 0, "cliques": 0,
                        "linkage_calls": 1, "rows_linked": n, "largest": n}
    under = dist <= _cutoff_in(dist.dtype, cutoff)
    np.fill_diagonal(under, False)
    # a row with nothing under the cutoff has no edge: the graph is built on
    # the others alone (a tenth of a catalogue of species representatives)
    active = np.flatnonzero(under.any(axis=1))
    adj = under if len(active) == n else under[np.ix_(active, active)]
    adj = adj & adj.T  # an edge needs both directions under the cutoff
    comp, n_comp = _components(adj)
    size = np.bincount(comp, minlength=n_comp)
    # a clique's members each have size - 1 neighbours
    loose = np.zeros(n_comp, dtype=bool)
    loose[comp[np.count_nonzero(adj, axis=1) != size[comp] - 1]] = True

    raw = np.arange(n, dtype=np.int64)  # a label of its own for every genome
    order = np.argsort(comp, kind="stable")  # members of a component ascend
    starts = np.concatenate([[0], np.cumsum(size)])
    raw[active] = active[order[starts[comp]]]  # a component: its first member
    next_label = n
    rows_linked = 0
    for c in np.flatnonzero(loose):
        members = active[order[starts[c]:starts[c + 1]]]
        of = dist if len(members) == n else dist[np.ix_(members, members)]
        sub, _ = cluster_hierarchical(of, cutoff, method=method)
        raw[members] = next_label + sub
        next_label += int(sub.max()) + 1
        rows_linked += len(members)
    components = n - len(active) + n_comp
    singletons = n - len(active) + int((size == 1).sum())
    linked = int(loose.sum())
    return _renumber_first_appearance(raw), {
        "genomes": n,
        "components": components,
        "singletons": singletons,
        "cliques": components - singletons - linked,
        "linkage_calls": linked,
        "rows_linked": rows_linked,
        "largest": int(size.max()) if n_comp else min(n, 1),
    }


@functools.partial(jax.jit, static_argnames=())
def _connected_components_labels(adj: jnp.ndarray) -> jnp.ndarray:
    """Min-label propagation over a boolean adjacency matrix [N, N].

    labels[i] converges to min node index reachable from i. Sweeps =
    graph diameter <= N; each sweep is one masked min-reduce (VPU-shaped).
    """
    n = adj.shape[0]
    adj = adj | jnp.eye(n, dtype=bool)
    init = jnp.arange(n, dtype=jnp.int32)

    def body(state):
        labels, _ = state
        # neighbor minimum: min over j with adj[i, j] of labels[j]
        big = jnp.int32(n)
        cand = jnp.where(adj, labels[None, :], big)
        new = jnp.minimum(labels, jnp.min(cand, axis=1))
        # two-hop acceleration: pointer jumping labels[labels]
        new = jnp.minimum(new, new[new])
        return new, jnp.any(new != labels)

    def cond(state):
        return state[1]

    labels, _ = jax.lax.while_loop(cond, body, (init, jnp.array(True)))
    return labels


def single_linkage_device(dist, cutoff: float) -> np.ndarray:
    """Single-linkage flat clusters at `cutoff` via on-device components.

    Exactly equals scipy single-linkage + fcluster(criterion='distance') —
    a cluster is a connected component of {d <= cutoff} (verified in tests).
    """
    adj = jnp.asarray(dist) <= cutoff
    labels = np.asarray(_connected_components_labels(adj))
    return _renumber_first_appearance(labels)


def sparse_average_linkage(
    n: int,
    ii: np.ndarray,
    jj: np.ndarray,
    dd: np.ndarray,
    cutoff: float,
    keep: float,
) -> tuple[np.ndarray, int]:
    """Average-linkage (UPGMA) flat clusters at `cutoff` from a SPARSE edge
    set — the streaming primary's linkage (so the 30k+ regime never falls
    back to single-linkage silently).

    Edges (ii[e], jj[e], dd[e]) are every pair with distance <= `keep`
    (the streaming retention bound, max(1-P_ani, warn_dist)); any pair NOT
    in the edge set therefore has distance > keep. UPGMA needs the average
    over ALL cross pairs of two clusters, so unobserved pairs enter the
    average at their LOWER BOUND `keep`. Consequences, both one-sided:

    - a rejected merge is always correctly rejected (the true average can
      only exceed the bound), so clusters are never under-merged relative
      to full-matrix UPGMA;
    - an accepted merge whose average involved NO unobserved pairs is
      exact. Merges that did involve unobserved pairs may over-merge (true
      distances > keep could pull the true average above the cutoff).

    Returns (labels 1..C by first appearance, number of accepted merges
    that involved unobserved pairs). A zero second value CERTIFIES the
    partition equals scipy full-matrix ``linkage(method='average')`` +
    ``fcluster(t=cutoff, criterion='distance')`` up to merge-tie ordering
    (tested). With the default warn_dist=0.25 retention band vs the 0.1
    cutoff, pulling an average from >0.25 to <=0.1 needs many very-tight
    known pairs against few unobserved ones — rare for genome clusters,
    and counted loudly when it happens.

    Host algorithm (lazy-heap agglomerative): O(E log E) heap traffic for
    E retained edges — at the 100k-genome scale this path serves, E is
    O(N * cluster_size), millions, not N^2. Only edge-connected cluster
    pairs ever become merge candidates: a pair with NO observed cross edge
    has average >= keep > cutoff by construction.

    The hot path is the C++ replica (native/linkage.cc — same total order
    over merge candidates, same float arithmetic, equality-tested
    label-for-label); this Python formulation is the always-available
    fallback and the semantic reference.
    """
    import heapq

    if n == 0:
        return np.zeros(0, dtype=np.int64), 0

    from drep_tpu.native import sparse_upgma_native

    native = sparse_upgma_native(n, ii, jj, dd, cutoff, keep)
    if native is not None:
        raw, approx_merges = native
        return _renumber_first_appearance(raw), approx_merges
    # symmetric neighbor maps: nbr[a][b] == nbr[b][a] == (sum_obs, cnt_obs)
    nbr: dict[int, dict[int, tuple[float, int]]] = {i: {} for i in range(n)}
    for a, b, d in zip(ii.tolist(), jj.tolist(), dd.tolist()):
        if a == b:
            continue
        cur = nbr[a].get(b)
        if cur is None or d < cur[0]:  # duplicates collapse to their min
            nbr[a][b] = nbr[b][a] = (float(d), 1)

    size = {i: 1 for i in range(n)}
    members: dict[int, list[int]] = {i: [i] for i in range(n)}
    alive = set(range(n))

    def bound(a: int, b: int, s: float, c: int) -> float:
        total = size[a] * size[b]
        return (s + (total - c) * keep) / total

    # singleton pairs: bound reduces to (d + 0*keep)/1 = d — build the
    # initial candidate list flat and heapify (O(E), vs O(E log E) pushes;
    # measured ~25% of the whole run at 100k nodes / 850k edges)
    heap: list[tuple[float, int, int, float, int]] = [
        (s, a, b, s, c)
        for a in range(n)
        for b, (s, c) in nbr[a].items()
        if a < b
    ]
    heapq.heapify(heap)

    next_id = n
    approx_merges = 0
    while heap:
        avg, a, b, s, c = heapq.heappop(heap)
        if avg > cutoff:
            break  # heap min is the global min over valid candidates
        if a not in alive or b not in alive:
            continue
        if nbr[a].get(b) != (s, c):
            continue  # stale entry (the pair's stats changed since push)
        if c < size[a] * size[b]:
            approx_merges += 1
        cid = next_id
        next_id += 1
        merged: dict[int, tuple[float, int]] = {}
        for src in (a, b):
            for x, (sx, cx) in nbr[src].items():
                if x == a or x == b:
                    continue
                del nbr[x][src]
                prev = merged.get(x)
                merged[x] = (prev[0] + sx, prev[1] + cx) if prev else (sx, cx)
        del nbr[a], nbr[b]
        alive.discard(a)
        alive.discard(b)
        alive.add(cid)
        size[cid] = size[a] + size[b]
        # small-to-large extend: O(N log N) total list moves across all
        # merges (a fresh concat per merge would be O(N^2) when a big
        # cluster assembles one genome at a time)
        ma, mb = members.pop(a), members.pop(b)
        if len(ma) < len(mb):
            ma, mb = mb, ma
        ma.extend(mb)
        members[cid] = ma
        nbr[cid] = merged
        for x, (sx, cx) in merged.items():
            nbr[x][cid] = (sx, cx)
            heapq.heappush(heap, (bound(cid, x, sx, cx), cid, x, sx, cx))

    labels = np.zeros(n, dtype=np.int64)
    for cid in alive:
        for node in members[cid]:
            labels[node] = cid
    return _renumber_first_appearance(labels), approx_merges


def sparse_linkage_account(
    n: int,
    ii: np.ndarray,
    jj: np.ndarray,
    dd: np.ndarray,
    labels: np.ndarray,
    cutoff: float,
    uncertified_merges: int,
) -> dict[str, int]:
    """What a linkage over the retained edges (ii, jj, dd) did, in
    :func:`cluster_by_components`' terms, for the job's record: one pass over
    the components of the graph of edges at or under `cutoff`. The edges are
    distinct pairs of two different genomes, as the streaming walk's upper
    triangle gives them (a pair given twice would count twice).

    `genomes`, `components`, `singletons`, `cliques` and `largest` mean what
    they mean there (a clique: a component of two or more whose every pair is
    an edge under the cutoff; whatever the merge order it is one cluster).
    `loose_components` are the others, the ones average linkage has to cut,
    `rows_loose` the genomes in them. `edges_retained` counts the distinct
    pairs handed in, `edges_under_cutoff` those at or under the cutoff,
    `edges_between_clusters` those whose ends `labels` puts in different
    clusters; `merges` is the merges the partition took (genomes less
    clusters), `uncertified_merges` the accepted merges that averaged over an
    unobserved pair (:func:`sparse_average_linkage`'s second value): 0
    certifies the partition equal to full-matrix UPGMA's.
    """
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    keys = ("genomes", "components", "singletons", "cliques", "loose_components", "rows_loose",
            "largest", "edges_retained", "edges_under_cutoff", "edges_between_clusters", "merges")
    if n == 0:
        return {**dict.fromkeys(keys, 0), "uncertified_merges": int(uncertified_merges)}
    ii, jj = np.asarray(ii), np.asarray(jj)
    under = np.asarray(dd) <= cutoff
    ui, uj = ii[under], jj[under]
    graph = coo_matrix((np.ones(len(ui), dtype=np.int8), (ui, uj)), shape=(n, n))
    n_comp, comp = connected_components(graph, directed=False)
    size = np.bincount(comp, minlength=n_comp)
    inside = np.bincount(comp[ui], minlength=n_comp)  # an edge lies in one component
    loose = inside != size * (size - 1) // 2
    labels = np.asarray(labels)
    singletons = int((size == 1).sum())
    return {
        "genomes": int(n),
        "components": int(n_comp),
        "singletons": singletons,
        "cliques": int(n_comp - singletons - loose.sum()),
        "loose_components": int(loose.sum()),
        "rows_loose": int(size[loose].sum()),
        "largest": int(size.max()),
        "edges_retained": int(len(ii)),
        "edges_under_cutoff": int(len(ui)),
        "edges_between_clusters": int(np.count_nonzero(labels[ii] != labels[jj])),
        "merges": int(n - len(np.unique(labels))),
        "uncertified_merges": int(uncertified_merges),
    }

"""The plain reference: what drep-tpu's answers should be, by NumPy and SciPy.

Independent of the program: nothing is imported from ``drep_tpu`` and nothing
the program computed is read. Inputs are the benchmark's own generated data
(sketches from ``generators/planted_sketches.py``). Everything is float64 on
the host.

Semantics, as upstream dRep and Mash define them and the configuration files
state them:

- a genome's bottom sketch is the ``sketch_size`` smallest distinct hashes
  of its k-mers, its scaled sketch every distinct hash <= 2^64/scale - 1
  (planted directly: no cell reads a FASTA);
- Mash distance: j = shared / s over the s smallest hashes of the union of
  two bottom sketches (s = the smaller sketch's size, at most sketch_size),
  d = -ln(2j / (1 + j)) / k, 1 where j = 0;
- ANI of a pair: max of the two containments of the scaled sketches, to the
  power 1/k; coverage of a by b: |a & b| / |a|;
- primary clusters: average linkage on Mash distance cut at 1 - P_ani;
  secondary: inside a primary cluster, average linkage on 1 - ANI (zeroed
  where either coverage is under cov_thresh) cut at 1 - S_ani.
"""

from __future__ import annotations

import numpy as np

# ---- distances --------------------------------------------------------------


def mash_jaccard(a: np.ndarray, b: np.ndarray, sketch_size: int) -> float:
    a, b = a[:sketch_size], b[:sketch_size]
    s = min(len(a), len(b), sketch_size)
    if s == 0:
        return 0.0
    union = np.union1d(a, b)[:s]
    shared = np.intersect1d(a, b, assume_unique=True)
    return float(np.count_nonzero(shared <= union[-1])) / s


def mash_distance(j: float, k: int) -> float:
    if j <= 0.0:
        return 1.0
    return float(min(1.0, max(0.0, -np.log(2.0 * j / (1.0 + j)) / k)))


def candidate_pairs(sketches: list[np.ndarray], sketch_size: int) -> np.ndarray:
    """Every pair (i < j) of sketches that share at least one hash, as an
    [m, 2] array: an inverted index, exact. A pair that shares none has
    Jaccard 0 and distance 1."""
    lens = np.array([min(len(s), sketch_size) for s in sketches])
    flat = np.concatenate([s[:sketch_size] for s in sketches])
    owner = np.repeat(np.arange(len(sketches)), lens)
    order = np.argsort(flat, kind="stable")  # stable: owners ascend inside a run
    flat, owner = flat[order], owner[order]
    n = len(sketches)
    keys = []
    for t in range(1, len(flat)):
        same = flat[:-t] == flat[t:]  # entries t apart that hold the same hash
        if not same.any():
            break
        keys.append(owner[:-t][same] * n + owner[t:][same])
    if not keys:
        return np.empty((0, 2), np.int64)
    keys = np.unique(np.concatenate(keys))
    return np.stack([keys // n, keys % n], axis=1)


def mash_edges(sketches: list[np.ndarray], sketch_size: int, k: int,
               lower_precision: bool = False) -> dict[tuple[int, int], float]:
    """{(i, j): distance} for every pair i < j at distance under 1.
    `lower_precision` is the control of "How correct is decided": the same
    arithmetic with the distance rounded to bfloat16."""
    out = {}
    for i, j in candidate_pairs(sketches, sketch_size):
        d = mash_distance(mash_jaccard(sketches[i], sketches[j], sketch_size), k)
        if d < 1.0:
            out[(int(i), int(j))] = to_bfloat16(d) if lower_precision else d
    return out


def to_bfloat16(x: float) -> float:
    """Round a value to the nearest bfloat16 (8 bits of significand)."""
    bits = np.array([x], np.float32).view(np.uint32)
    bits = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))) & np.uint32(0xFFFF0000)
    return float(bits.view(np.float32)[0])


def containment(a: np.ndarray, b: np.ndarray) -> tuple[float, float]:
    """(coverage of a by b, coverage of b by a) of two scaled sketches."""
    inter = len(np.intersect1d(a, b, assume_unique=True))
    return (inter / len(a) if len(a) else 0.0, inter / len(b) if len(b) else 0.0)


def ani_from_containment(c_ab: float, c_ba: float, k: int, lower_precision: bool = False) -> float:
    c = max(c_ab, c_ba)
    ani = float(c ** (1.0 / k)) if c > 0 else 0.0
    return to_bfloat16(ani) if lower_precision else ani


# ---- clustering -------------------------------------------------------------


def _average_linkage(dist: np.ndarray, cutoff: float) -> np.ndarray:
    import scipy.cluster.hierarchy as sch
    import scipy.spatial.distance as ssd

    if len(dist) == 1:
        return np.ones(1, np.int64)
    link = sch.linkage(ssd.squareform(dist, checks=False), method="average")
    return sch.fcluster(link, t=cutoff, criterion="distance")


def primary_partition(n: int, edges: dict[tuple[int, int], float], cutoff: float) -> list[frozenset]:
    """Average-linkage clusters of n genomes at `cutoff`, from the sparse
    distances (an absent pair is at distance 1). Exact: two components of
    the graph of pairs under 1 are at average distance 1 and never merge
    under a cutoff below 1."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components

    if edges:
        ij = np.array(list(edges), np.int64)
        graph = sp.coo_matrix((np.ones(len(ij)), (ij[:, 0], ij[:, 1])), shape=(n, n))
    else:
        graph = sp.coo_matrix((n, n))
    _, comp = connected_components(graph, directed=False)
    members: dict[int, list[int]] = {}
    for g, c in enumerate(comp):
        members.setdefault(int(c), []).append(g)
    inside: dict[int, list] = {}
    for (i, j), d in edges.items():
        inside.setdefault(int(comp[i]), []).append((i, j, d))
    out = []
    for c, group in members.items():
        if len(group) == 1:
            out.append(frozenset(group))
            continue
        pos = {g: x for x, g in enumerate(group)}
        dist = np.ones((len(group), len(group)))
        np.fill_diagonal(dist, 0.0)
        for i, j, d in inside[c]:
            dist[pos[i], pos[j]] = dist[pos[j], pos[i]] = d
        labels = _average_linkage(dist, cutoff)
        for lab in np.unique(labels):
            out.append(frozenset(group[x] for x in np.flatnonzero(labels == lab)))
    return out


def secondary_of_cluster(scaled: list[np.ndarray], k: int, s_ani: float, cov_thresh: float,
                         lower_precision: bool = False):
    """One primary cluster's secondary stage. Returns (ani [m, m], cov
    [m, m] with cov[i, j] = coverage of i by j, labels [m])."""
    m = len(scaled)
    ani = np.ones((m, m))
    cov = np.ones((m, m))
    for i in range(m):
        for j in range(i + 1, m):
            cov[i, j], cov[j, i] = containment(scaled[i], scaled[j])
            ani[i, j] = ani[j, i] = ani_from_containment(cov[i, j], cov[j, i], k, lower_precision)
    gate = (cov >= cov_thresh) & (cov.T >= cov_thresh)
    dist = 1.0 - np.where(gate, ani, 0.0)
    np.fill_diagonal(dist, 0.0)
    return ani, cov, _average_linkage(dist, 1.0 - s_ani)


def partition_of(labels) -> set[frozenset]:
    """{genome: label} or a sequence of labels -> the set of clusters."""
    items = labels.items() if isinstance(labels, dict) else enumerate(labels)
    groups: dict = {}
    for g, lab in items:
        groups.setdefault(lab, set()).add(g)
    return {frozenset(v) for v in groups.values()}


def partition_mismatch(got: set[frozenset], want: set[frozenset]) -> int:
    """How many genomes sit in a cluster that the other partition lacks."""
    return sum(len(c) for c in got ^ want if c in got)

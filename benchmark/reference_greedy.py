"""The plain reference of a `compare` job under greedy secondary clustering:
what `--greedy_secondary_clustering` means, written as the rule reads.

NumPy and SciPy only. Nothing of the program is imported, nothing it
computed is read, and nothing of its design is here: no blocks, no
representative tiles, no indicator matrices, no batches. Inputs are the
benchmark's own planted sketches (``generators/planted_release.py``) and each
genome's `n_kmers`. Everything is float64 on the host.

- Primary clusters, as ``reference.py``: Mash distance from the bottom
  sketches (j = shared / s over the s smallest hashes of the union, d =
  -ln(2j / (1 + j)) / k, 1 where nothing is shared), scipy's average linkage
  cut at 1 - P_ani. Genomes that share no hash with one another, directly or
  through others, lie at distance 1 and never merge under a cut below 1, so
  the linkage runs once for each connected group (``reference.py``'s
  argument), over that group's dense matrix
  (``reference_species.mash_matrix``: ``reference.py``'s values, bit for bit).
- Secondary clusters, for every primary cluster of two or more, the greedy
  rule (upstream dRep's `--greedy_secondary_clustering`): visit the genomes by
  `n_kmers`, most first, ties in input order. Compare the genome with every
  representative that exists at that moment, one pair at a time: the
  intersection of the two sorted hash arrays, the coverage of each by the
  other, ANI = the larger coverage to the power 1/k (the program's estimator's
  definition, ``reference.ani_from_containment``). It joins the
  representative of the highest ANI among those at ANI >= S_ani with both
  coverages >= cov_thresh (the earliest of equals), and founds a cluster of
  its own if there is none. Every comparison made is a row (querry,
  reference): the Ndb of such a job holds these rows and no others.

The rule is the same for a cluster of 5 and of 500; the program's two routes
(matrices from the batched call, the engine) both have to equal it.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference as ref
from benchmark import reference_species as refs

# ---- primary ----------------------------------------------------------------------


def connected_groups(bottom: list[np.ndarray], sketch_size: int) -> list[np.ndarray]:
    """The genomes in groups that share bottom hashes, directly or through
    others; each group sorted, the groups by their first genome."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components

    n = len(bottom)
    lens = np.array([min(len(b), sketch_size) for b in bottom])
    flat = np.concatenate([b[:sketch_size] for b in bottom])
    owner = np.repeat(np.arange(n), lens)
    order = np.argsort(flat, kind="stable")
    flat, owner = flat[order], owner[order]
    same = flat[:-1] == flat[1:]  # neighbours in a run of one hash: the run is connected
    graph = sp.coo_matrix((np.ones(int(same.sum())), (owner[:-1][same], owner[1:][same])),
                          shape=(n, n))
    _, comp = connected_components(graph, directed=False)
    by_comp = np.argsort(comp, kind="stable")
    groups = np.split(by_comp, np.flatnonzero(np.diff(comp[by_comp])) + 1)
    return sorted(groups, key=lambda g: int(g[0]))


def primary(bottom: list[np.ndarray], sketch_size: int, k: int, cutoff: float,
            lower_precision: bool = False) -> tuple[np.ndarray, dict]:
    """(labels [n] from 1, {"i", "j", "dist"}: every pair i < j at a distance
    under 1). `lower_precision` is the control: each distance under 1 rounded
    to bfloat16 before the linkage sees it."""
    labels = np.zeros(len(bottom), np.int64)
    ii, jj, dd = [], [], []
    for group in connected_groups(bottom, sketch_size):
        if len(group) == 1:
            labels[group] = labels.max() + 1
            continue
        dist = refs.mash_matrix([bottom[g] for g in group], sketch_size, k, lower_precision)
        labels[group] = labels.max() + ref._average_linkage(dist, cutoff)
        a, b = np.nonzero(np.triu(dist < 1.0, 1))
        ii.append(group[a]); jj.append(group[b]); dd.append(dist[a, b])  # noqa: E702
    cat = (lambda parts, dtype: np.concatenate(parts) if parts else np.zeros(0, dtype))
    return labels, {"i": cat(ii, np.int64), "j": cat(jj, np.int64), "dist": cat(dd, np.float64)}


# ---- secondary: the greedy rule -----------------------------------------------------


def shared_hashes(a: np.ndarray, b: np.ndarray) -> int:
    """|a & b| of two sorted arrays of distinct hashes."""
    if not len(a) or not len(b):
        return 0
    at = np.searchsorted(b, a)
    return int(np.count_nonzero(b[np.minimum(at, len(b) - 1)] == a))


def visiting_order(n_kmers) -> list[int]:
    """Most k-mers first, ties in input order."""
    return sorted(range(len(n_kmers)), key=lambda t: -int(n_kmers[t]))


def greedy_of_cluster(scaled: list[np.ndarray], n_kmers, k: int, s_ani: float, cov_thresh: float,
                      lower_precision: bool = False, order=None) -> tuple[np.ndarray, list[tuple]]:
    """One primary cluster under the greedy rule: (labels [m] from 1 in the
    order clusters were founded, rows (querry, reference, ani, coverage of the
    querry by the reference, coverage of the reference by the querry) in the
    order the comparisons were made). `lower_precision` is the control: ANI
    and coverages rounded to bfloat16 before the rule sees them. `order`
    replaces the visiting order (the tests' wrong answer: another order
    founds other representatives and consumes other pairs)."""
    labels = np.zeros(len(scaled), np.int64)
    reps: list[int] = []
    rows = []
    for t in visiting_order(n_kmers) if order is None else order:
        best_ani, best = -1.0, 0
        for r in reps:
            shared = shared_hashes(scaled[t], scaled[r])
            cov_t = shared / len(scaled[t]) if len(scaled[t]) else 0.0
            cov_r = shared / len(scaled[r]) if len(scaled[r]) else 0.0
            ani = ref.ani_from_containment(cov_t, cov_r, k, lower_precision)
            if lower_precision:
                cov_t, cov_r = ref.to_bfloat16(cov_t), ref.to_bfloat16(cov_r)
            rows.append((t, r, ani, cov_t, cov_r))
            if ani >= s_ani and cov_t >= cov_thresh and cov_r >= cov_thresh and ani > best_ani:
                best_ani, best = ani, labels[r]
        if not best:
            reps.append(t)
            best = len(reps)
        labels[t] = best
    return labels, rows


# ---- the whole job -------------------------------------------------------------------


def compare_greedy(bottom: list[np.ndarray], scaled: list[np.ndarray], n_kmers, params: dict,
                   lower_precision: bool = False) -> dict:
    """What the job's tables should hold, over genome numbers:

    "primary" [n] and "secondary" [n]: cluster labels (secondary labels are
    numbered over the collection); "mash": {"i", "j", "dist"}, pairs i < j
    under distance 1; "rows": {"q", "r", "ani", "cov_q", "cov_r"}, one entry
    a comparison the rule makes."""
    k, s = int(params["kmer_size"]), int(params["sketch_size"])
    labels, mash = primary(bottom, s, k, 1.0 - params["P_ani"], lower_precision)
    secondary = np.zeros(len(bottom), np.int64)
    rows = []
    for label in range(1, int(labels.max()) + 1 if len(labels) else 1):
        group = np.flatnonzero(labels == label)
        if len(group) == 1:
            secondary[group] = secondary.max() + 1
            continue
        found, made = greedy_of_cluster([scaled[g] for g in group], np.asarray(n_kmers)[group], k,
                                        params["S_ani"], params["cov_thresh"], lower_precision)
        secondary[group] = secondary.max() + found
        rows += [(group[t], group[r], ani, cq, cr) for t, r, ani, cq, cr in made]
    table = np.array(rows, np.float64).reshape(-1, 5)
    return {"primary": labels, "secondary": secondary, "mash": mash,
            "rows": {"q": table[:, 0].astype(np.int64), "r": table[:, 1].astype(np.int64),
                     "ani": table[:, 2], "cov_q": table[:, 3], "cov_r": table[:, 4]}}

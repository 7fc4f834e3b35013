"""The plain reference of a `compare` job on planted genera
(``generators/planted_genera.py``): ``reference_greedy.compare_greedy`` (the
primary partition by full-matrix average linkage inside every connected group
of the shares-a-hash graph, absent pairs at 1: exact for a component that is
no clique, a genus of 768 genomes is one group and one 768 x 768 matrix; the
greedy scan; every Mdb pair), plus what a sound job's record has to say of the
linkage it ran: counted here from the exact pairs, with nothing of the program.

NumPy and SciPy only, float64 on the host. A Mash distance is a function of
an integer count of shared hashes among 1,000, so no pair lies within 7e-4 of
the cutoff 0.1 (65 shared: 0.10018, 66: 0.09941) nor within 6e-3 of the
retention bound 0.25 (3 shared: 0.2438, 2: 0.2631): counting the pairs at or
under either is exact, whatever float32 the program took its logarithm in.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference_greedy as rg


def loose_components(n: int, i: np.ndarray, j: np.ndarray) -> tuple[int, int]:
    """(components of two or more that are not cliques, genomes in them) of
    the graph whose edges are the distinct pairs (i, j)."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import connected_components

    n_comp, comp = connected_components(
        sp.coo_matrix((np.ones(len(i), np.int8), (i, j)), shape=(n, n)), directed=False)
    size = np.bincount(comp, minlength=n_comp)
    loose = np.bincount(comp[i], minlength=n_comp) != size * (size - 1) // 2
    return int(loose.sum()), int(size[loose].sum())


def linkage_counts(n: int, mash: dict, primary: np.ndarray, params: dict) -> dict:
    """What the record's `primary_linkage` has to hold of a job whose retained
    edges are the pairs of `mash` at or under `retention_dist` and whose
    partition is `primary`."""
    i, j, d = mash["i"], mash["j"], mash["dist"]
    kept = d <= params["retention_dist"]
    under = d <= 1.0 - params["P_ani"]
    loose, rows_loose = loose_components(n, i[under], j[under])
    return {"loose_components": loose, "rows_loose": rows_loose,
            "edges_retained": int(kept.sum()), "edges_under_cutoff": int(under.sum()),
            "edges_between_clusters": int((kept & (primary[i] != primary[j])).sum()),
            "merges": int(n - len(np.unique(primary)))}


def compare_genera(bottom: list[np.ndarray], scaled: list[np.ndarray], n_kmers, params: dict,
                   lower_precision: bool = False) -> dict:
    """``reference_greedy.compare_greedy``'s answers plus "linkage"
    (``linkage_counts``). `lower_precision` is the control: distances, ANIs and
    coverages rounded to bfloat16 before the linkage, the counts and the
    greedy rule see them."""
    want = rg.compare_greedy(bottom, scaled, n_kmers, params, lower_precision)
    want["linkage"] = linkage_counts(len(bottom), want["mash"], want["primary"], params)
    return want

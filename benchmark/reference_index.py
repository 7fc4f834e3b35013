"""The plain reference of an index that grew: the union of the index and its
batch clustered FROM SCRATCH, at full size, no sample, and what an update that
is sound has to have recomputed. NumPy and SciPy only; nothing of the program
is imported and nothing it computed is read.

The semantics are ``reference.py``'s, computed a cluster at a time by
``reference_greedy.primary`` (Mash over the groups that share bottom hashes,
average linkage at 1 - P_ani) and ``reference_species.secondary_of_cluster``
(all-pairs containment ANI and coverage of a primary cluster, average linkage
at 1 - S_ani), which agree with ``reference.py`` to the last bit
(benchmark/tests/test_species_cell.py, test_greedy_cell.py). On top of them,
as ``reference_fasta.py`` states them:

- centrality of a genome: the mean ANI to the other members of its secondary
  cluster (0 alone); score = N50W x log10(N50) + sizeW x log10(length) +
  centW x (centrality - S_ani) (an index scores quality-uninformed); the
  winner of a secondary cluster has the highest score, the first name on a
  tie;
- a union cluster has a CHANGED MEMBER SET if no primary cluster of the index
  alone, clustered from scratch by the same rule, holds exactly its members.
  An update has to recompute exactly those (a cluster founded inside the
  batch and a new singleton are among them) and may reuse every other.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference as ref
from benchmark import reference_greedy as rg
from benchmark import reference_species as refs


def from_scratch(bottom: list[np.ndarray], scaled: list[np.ndarray], names: list[str],
                 length: np.ndarray, n50: np.ndarray, params: dict,
                 lower_precision: bool = False) -> dict:
    """The union clustered from scratch. Returns "primary" [n] labels from 1,
    "mash" {"i", "j", "dist"} (every pair i < j under distance 1),
    "secondary" [n] labels numbered over the collection, "pairs" {"q", "r",
    "ani", "cov"}: every ordered pair inside a primary cluster (cov = coverage
    of q by r), "score" [n], "winners" (genome numbers, sorted).
    `lower_precision` is the control: distances and ANIs rounded to bfloat16
    before anything is derived from them."""
    k, s = int(params["kmer_size"]), int(params["sketch_size"])
    n = len(bottom)
    primary, mash = rg.primary(bottom, s, k, 1.0 - params["P_ani"], lower_precision)
    secondary = np.zeros(n, np.int64)
    centrality = np.zeros(n)
    qq, rr, aa, cc = [], [], [], []
    by_label = np.argsort(primary, kind="stable")
    for group in np.split(by_label, np.flatnonzero(np.diff(primary[by_label])) + 1):
        if len(group) == 1:
            secondary[group] = secondary.max() + 1
            continue
        ani, cov, sec = refs.secondary_of_cluster([scaled[g] for g in group], k, params["S_ani"],
                                                  params["cov_thresh"], lower_precision)
        secondary[group] = secondary.max() + sec
        mates = (sec[:, None] == sec[None, :]) & ~np.eye(len(group), dtype=bool)
        count = mates.sum(axis=1)
        centrality[group] = np.where(count > 0, (ani * mates).sum(axis=1) / np.maximum(count, 1), 0.0)
        x, y = np.nonzero(~np.eye(len(group), dtype=bool))
        qq.append(group[x]); rr.append(group[y]); aa.append(ani[x, y]); cc.append(cov[x, y])  # noqa: E702
    w = params["weights"]
    score = (w["N50"] * np.log10(np.maximum(n50, 1)) + w["size"] * np.log10(np.maximum(length, 1))
             + w["centrality"] * (centrality - params["S_ani"]))
    winners = {}
    for g in sorted(range(n), key=lambda g: names[g]):  # the first name wins a tie
        best = winners.get(int(secondary[g]))
        if best is None or score[g] > score[best]:
            winners[int(secondary[g])] = g
    cat = (lambda parts, dtype: np.concatenate(parts) if parts else np.zeros(0, dtype))
    return {"primary": primary, "mash": mash, "secondary": secondary,
            "pairs": {"q": cat(qq, np.int64), "r": cat(rr, np.int64),
                      "ani": cat(aa, np.float64), "cov": cat(cc, np.float64)},
            "score": score, "winners": np.array(sorted(winners.values()), np.int64)}


def changed_clusters(union_primary: np.ndarray, old_primary: np.ndarray) -> list[np.ndarray]:
    """The primary clusters of the union (member arrays, by first member)
    whose member set no primary cluster of the index alone holds. The index
    is the union's first ``len(old_primary)`` genomes."""
    old = ref.partition_of(old_primary)
    out = []
    for c in ref.partition_of(union_primary):
        if c not in old:
            out.append(np.array(sorted(c), np.int64))
    return sorted(out, key=lambda c: int(c[0]))


def expected_work(union_primary: np.ndarray, old_primary: np.ndarray) -> dict:
    """What a sound update of the index to the union recomputes: the counters
    of the program's `index` record section that the answers decide."""
    changed = changed_clusters(union_primary, old_primary)
    total = len(ref.partition_of(union_primary))
    return {"clusters_recomputed": len(changed), "clusters_reused": total - len(changed),
            "members_recomputed": int(sum(len(c) for c in changed)),
            "secondary_calls": sum(len(c) > 1 for c in changed),
            "singletons_scored": sum(len(c) == 1 for c in changed)}

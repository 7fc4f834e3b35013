"""The plain reference at the size of one deep cluster: the semantics of
``reference.py``, value for value, computed for a whole cluster at once.

``reference.py`` compares a pair at a time (`np.intersect1d`, `np.union1d`):
right for thousands of small clusters, a quarter of an hour for the 523,776
pairs of 1,024 genomes in one. Here the same integer counts come from array
arithmetic over the cluster's own sorted vocabulary, and every float is then
made by ``reference.py``'s own scalar functions from those counts, so the
two agree to the last bit (benchmark/tests/test_species_cell.py,
tests/test_secondary_deep.py). NumPy and SciPy only; nothing of the program
is imported and nothing it computed is read. Everything after the counts is
float64 on the host.

- intersection sizes of scaled sketches: the product of the 0/1 indicator
  matrix over the cluster's vocabulary with its transpose, in column blocks,
  in float32 (a block's counts stay under 2^24, so every partial product is
  exact) and added up in int64. Hashes that one genome alone holds are left
  out of the product: they are in no intersection.
- Mash's shared count over the s smallest hashes of a union: for a pair
  (a, b), a hash x of a lies among them iff the union holds at most s hashes
  up to x, and that number is (hashes of a up to x) + (hashes of b up to x)
  - (hashes of both up to x). All three are running sums over a's hashes.
"""

from __future__ import annotations

import numpy as np

from benchmark import reference as ref

COLUMN_BLOCK = 16384  # vocabulary columns a product takes at a time (under 2^24)


def _by_unique(keys: np.ndarray, fn) -> np.ndarray:
    """fn (a scalar function of reference.py) over an array, called once
    for each distinct value."""
    uniq, inverse = np.unique(keys, return_inverse=True)
    return np.array([fn(u) for u in uniq], np.float64)[inverse].reshape(keys.shape)


# ---- Mash --------------------------------------------------------------------


def mash_shared_counts(bottom: list[np.ndarray], sketch_size: int) -> tuple[np.ndarray, np.ndarray]:
    """(shared [n, n], s [n, n]): for every pair, how many hashes both
    bottom sketches hold among the s smallest of their union, and s itself
    (the smaller sketch's size, at most `sketch_size`)."""
    rows = [b[:sketch_size] for b in bottom]
    n = len(rows)
    lens = np.array([len(r) for r in rows], np.int64)
    s = np.minimum(np.minimum.outer(lens, lens), sketch_size)
    vocab = np.unique(np.concatenate(rows)) if n else np.zeros(0, np.uint64)
    ranks = [np.searchsorted(vocab, r) for r in rows]
    # has[x, j]: genome j holds hash x; upto[x, j]: how many of its hashes are <= x
    has = np.zeros((len(vocab), n), np.int16)
    for j, r in enumerate(ranks):
        has[r, j] = 1
    upto = np.cumsum(has, axis=0, dtype=np.int16)  # a sketch holds far under 2^15 hashes
    shared = np.zeros((n, n), np.int64)
    for i, r in enumerate(ranks[:-1]):
        in_b = has[r, i + 1:]  # [len a, genomes after a]: a's hash p is in genome j
        both_upto = np.cumsum(in_b, axis=0, dtype=np.int16)
        union_upto = np.arange(1, len(r) + 1, dtype=np.int16)[:, None] + upto[r, i + 1:] - both_upto
        shared[i, i + 1:] = np.sum(in_b * (union_upto <= s[i, i + 1:][None, :]), axis=0, dtype=np.int64)
    shared += shared.T  # the count is symmetric
    np.fill_diagonal(shared, lens.clip(max=sketch_size))
    return shared, s


def mash_matrix(bottom: list[np.ndarray], sketch_size: int, k: int,
                lower_precision: bool = False) -> np.ndarray:
    """[n, n] Mash distances as ``reference.mash_distance`` gives them for
    ``reference.mash_jaccard``'s Jaccard; 0 on the diagonal. `lower_precision`
    is the control: each distance under 1 rounded to bfloat16."""
    shared, s = mash_shared_counts(bottom, sketch_size)

    def one(key: int) -> float:
        count, size = divmod(int(key), sketch_size + 1)
        d = ref.mash_distance(float(count) / size if size else 0.0, k)
        return ref.to_bfloat16(d) if lower_precision and d < 1.0 else d

    dist = _by_unique(shared * (sketch_size + 1) + s, one)
    np.fill_diagonal(dist, 0.0)
    return dist


# ---- containment -------------------------------------------------------------


def intersection_counts(scaled: list[np.ndarray]) -> np.ndarray:
    """[m, m] int64: |a & b| for every pair of sorted unique hash arrays."""
    m = len(scaled)
    lens = np.array([len(s) for s in scaled], np.int64)
    inter = np.zeros((m, m), np.int64)
    if m and lens.sum():
        flat = np.concatenate(scaled)
        owner = np.repeat(np.arange(m), lens)
        _, column, held_by = np.unique(flat, return_inverse=True, return_counts=True)
        several = held_by >= 2  # a hash of one genome alone is in no intersection
        dense = np.cumsum(several) - 1
        keep = several[column]
        owner, column = owner[keep], dense[column[keep]]
        order = np.argsort(column, kind="stable")
        owner, column = owner[order], column[order]
        width = int(several.sum())
        for lo in range(0, width, COLUMN_BLOCK):
            a, b = np.searchsorted(column, (lo, lo + COLUMN_BLOCK))
            block = np.zeros((m, min(COLUMN_BLOCK, width - lo)), np.float32)
            block[owner[a:b], column[a:b] - lo] = 1.0
            inter += (block @ block.T).astype(np.int64)
    np.fill_diagonal(inter, lens)
    return inter


def secondary_of_cluster(scaled: list[np.ndarray], k: int, s_ani: float, cov_thresh: float,
                         lower_precision: bool = False):
    """``reference.secondary_of_cluster`` for a whole cluster at once: (ani
    [m, m], cov [m, m] with cov[i, j] = coverage of i by j, labels [m])."""
    m = len(scaled)
    inter = intersection_counts(scaled)
    lens = np.array([len(s) for s in scaled], np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        cov = np.where(lens[:, None] > 0, inter / lens[:, None], 0.0)
    ani = _by_unique(np.maximum(cov, cov.T),
                     lambda c: ref.ani_from_containment(float(c), 0.0, k, lower_precision))
    np.fill_diagonal(cov, 1.0)
    np.fill_diagonal(ani, 1.0)
    gate = (cov >= cov_thresh) & (cov.T >= cov_thresh)
    dist = 1.0 - np.where(gate, ani, 0.0)
    np.fill_diagonal(dist, 0.0)
    return ani, cov, ref._average_linkage(dist, 1.0 - s_ani) if m else np.zeros(0, np.int64)


def primary_labels(dist: np.ndarray, cutoff: float) -> np.ndarray:
    """Average-linkage clusters of the dense Mash matrix at `cutoff`: what
    ``reference.primary_partition`` gives when every pair is an edge (a pair
    at distance 1 is an edge of that length there too)."""
    return ref._average_linkage(dist, cutoff)

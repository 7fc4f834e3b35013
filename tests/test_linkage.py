"""Clustering equivalence: device single-linkage vs scipy; determinism."""

import numpy as np
import pytest
import scipy.cluster.hierarchy as sch
import scipy.spatial.distance as ssd

from drep_tpu.ops.linkage import (
    _renumber_first_appearance,
    cluster_by_components,
    cluster_hierarchical,
    single_linkage_device,
)


def _random_dist(rng, n):
    d = rng.random((n, n)).astype(np.float64)
    d = (d + d.T) / 2
    np.fill_diagonal(d, 0.0)
    return d


def test_device_single_linkage_equals_scipy(rng):
    for n in (2, 5, 17, 60):
        d = _random_dist(rng, n)
        for cutoff in (0.05, 0.25, 0.5, 0.9):
            got = single_linkage_device(d, cutoff)
            link = sch.linkage(ssd.squareform(d, checks=False), method="single")
            want = _renumber_first_appearance(sch.fcluster(link, t=cutoff, criterion="distance"))
            assert np.array_equal(got, want), (n, cutoff)


def test_cluster_hierarchical_average(rng):
    d = _random_dist(rng, 20)
    labels, link = cluster_hierarchical(d, 0.3, method="average")
    want = _renumber_first_appearance(
        sch.fcluster(sch.linkage(ssd.squareform(d, checks=False), method="average"), t=0.3, criterion="distance")
    )
    assert np.array_equal(labels, want)
    assert link.shape == (19, 4)


def test_single_genome():
    labels, link = cluster_hierarchical(np.zeros((1, 1)), 0.1)
    assert labels.tolist() == [1]
    assert len(link) == 0


def test_all_identical_one_cluster():
    d = np.zeros((6, 6))
    labels, _ = cluster_hierarchical(d, 0.1)
    assert labels.tolist() == [1] * 6
    assert np.array_equal(single_linkage_device(d, 0.1), labels)


def test_all_distant_all_singletons():
    n = 8
    d = np.ones((n, n))
    np.fill_diagonal(d, 0.0)
    labels, _ = cluster_hierarchical(d, 0.1)
    assert labels.tolist() == list(range(1, n + 1))
    assert np.array_equal(single_linkage_device(d, 0.1), labels)


def test_first_appearance_numbering():
    assert _renumber_first_appearance(np.array([5, 5, 2, 9, 2])).tolist() == [1, 1, 2, 3, 2]


# ---- sparse average linkage (the streaming primary's UPGMA) -----------------


def _edges_below(d: np.ndarray, keep: float):
    ii, jj = np.nonzero(np.triu(d <= keep, 1))
    return ii, jj, d[ii, jj]


def _scipy_average_labels(d: np.ndarray, cutoff: float) -> np.ndarray:
    link = sch.linkage(ssd.squareform(d, checks=False), method="average")
    return _renumber_first_appearance(sch.fcluster(link, t=cutoff, criterion="distance"))


def _blocky_dist(rng, sizes, within=(0.0, 0.08), between=(0.12, 0.6)):
    """Planted blocks: tight within, spread between — the genome-cluster
    shape the streaming path exists for."""
    n = sum(sizes)
    d = rng.uniform(*between, size=(n, n))
    o = 0
    for s in sizes:
        d[o : o + s, o : o + s] = rng.uniform(*within, size=(s, s))
        o += s
    d = (d + d.T) / 2
    np.fill_diagonal(d, 0.0)
    return d


def test_sparse_average_equals_scipy_full_retention(rng):
    """With every pair retained (keep >= max dist), sparse UPGMA must equal
    scipy full-matrix average linkage exactly."""
    from drep_tpu.ops.linkage import sparse_average_linkage

    for sizes in ([4, 7, 5], [1, 9, 3, 6], [2, 2]):
        d = _blocky_dist(rng, sizes)
        ii, jj, dd = _edges_below(d, keep=1.0)
        labels, approx = sparse_average_linkage(len(d), ii, jj, dd, 0.10, 1.0)
        assert approx == 0
        assert np.array_equal(labels, _scipy_average_labels(d, 0.10)), sizes


def test_sparse_average_equals_scipy_banded_retention(rng):
    """With the realistic retention band (keep=0.25 vs cutoff 0.10), merges
    never touch unobserved pairs on blocky data, so the partition still
    equals scipy exactly and the exactness certificate holds."""
    from drep_tpu.ops.linkage import sparse_average_linkage

    for seed_sizes in ([6, 8, 4, 10], [3, 12, 5]):
        d = _blocky_dist(rng, seed_sizes, between=(0.3, 0.9))
        ii, jj, dd = _edges_below(d, keep=0.25)
        labels, approx = sparse_average_linkage(len(d), ii, jj, dd, 0.10, 0.25)
        assert approx == 0
        assert np.array_equal(labels, _scipy_average_labels(d, 0.10)), seed_sizes


def test_sparse_average_differs_from_single_linkage(rng):
    """The case the silent fallback got wrong: a near-threshold bridge that
    single-linkage follows but average linkage rejects."""
    from drep_tpu.ops.linkage import sparse_average_linkage
    from drep_tpu.parallel.streaming import connected_components

    # two tight pairs bridged by ONE 0.09 edge; the other three cross
    # distances are ~0.2, so the cross-cluster average is ~0.17 > 0.10
    d = np.array(
        [
            [0.00, 0.02, 0.09, 0.20],
            [0.02, 0.00, 0.20, 0.21],
            [0.09, 0.20, 0.00, 0.03],
            [0.20, 0.21, 0.03, 0.00],
        ]
    )
    ii, jj, dd = _edges_below(d, keep=0.25)
    labels, approx = sparse_average_linkage(4, ii, jj, dd, 0.10, 0.25)
    assert approx == 0
    assert np.array_equal(labels, _scipy_average_labels(d, 0.10))
    assert labels.tolist() == [1, 1, 2, 2]  # average keeps the pairs apart
    in_cluster = dd <= 0.10
    single = connected_components(4, ii[in_cluster], jj[in_cluster])
    assert single.tolist() == [1, 1, 1, 1]  # single-linkage bridges them


def test_sparse_average_conservative_on_unobserved(rng):
    """Unobserved pairs enter at the retention bound: a merge that the
    bound keeps above the cutoff is rejected even though the observed
    edges alone would average below it."""
    from drep_tpu.ops.linkage import sparse_average_linkage

    # clusters {0,1} and {2,3}: one observed cross edge at 0.02, the other
    # three cross pairs unobserved (> keep=0.25). Observed-only average
    # would be 0.02 <= 0.10 and wrongly merge; the bound gives
    # (0.02 + 3*0.25)/4 = 0.19 > 0.10.
    ii = np.array([0, 2, 0])
    jj = np.array([1, 3, 2])
    dd = np.array([0.01, 0.01, 0.02])
    labels, _ = sparse_average_linkage(4, ii, jj, dd, 0.10, 0.25)
    assert labels.tolist() == [1, 1, 2, 2]


def test_streaming_rejects_unsupported_cluster_alg(rng):
    from drep_tpu.ops.minhash import PackedSketches
    from drep_tpu.parallel.streaming import streaming_primary_clusters

    ids = np.sort(rng.integers(0, 1000, size=(4, 64), dtype=np.int32), axis=1)
    packed = PackedSketches(
        ids=ids, counts=np.full(4, 64, np.int32), names=list("abcd")
    )
    import pytest

    with pytest.raises(ValueError, match="average or single"):
        streaming_primary_clusters(packed, 21, 0.9, cluster_alg="complete")


def _python_sparse_upgma(n, ii, jj, dd, cutoff, keep, monkeypatch):
    """Pin the pure-Python reference path (native disabled)."""
    from drep_tpu.ops.linkage import sparse_average_linkage

    monkeypatch.setenv("DREP_TPU_NO_NATIVE", "1")
    out = sparse_average_linkage(n, ii, jj, dd, cutoff, keep)
    monkeypatch.delenv("DREP_TPU_NO_NATIVE")
    return out


def test_native_sparse_upgma_matches_python(rng, monkeypatch):
    """native/linkage.cc is a bit-exact replica of the Python sparse UPGMA:
    identical labels AND approx-merge counts on random graphs, blocky
    graphs, banded retention, and graphs with heavy distance ties (the
    regime where any ordering difference between the two heaps would
    surface as a different partition)."""
    import drep_tpu.native as native_mod
    from drep_tpu.ops.linkage import sparse_average_linkage

    if native_mod.get_library() is None:
        import pytest

        pytest.skip("no compiler: native path unavailable")

    cases = []
    for sizes in ([5, 8, 3], [1, 14, 6, 9], [2, 2, 2, 2, 2]):
        d = _blocky_dist(rng, sizes)
        cases.append((d, 0.10, 0.25))
        cases.append((d, 0.10, 1.0))
    # tie-rich: distances quantized to a coarse grid so many candidate
    # averages collide exactly
    for n_nodes in (12, 30, 64):
        d = np.round(rng.uniform(0, 0.4, size=(n_nodes, n_nodes)), 2)
        d = (d + d.T) / 2
        np.fill_diagonal(d, 0.0)
        cases.append((d, 0.10, 0.25))
        cases.append((d, 0.15, 0.5))
    for d, cutoff, keep in cases:
        ii, jj, dd = _edges_below(d, keep=keep)
        want_labels, want_approx = _python_sparse_upgma(
            len(d), ii, jj, dd, cutoff, keep, monkeypatch
        )
        got_labels, got_approx = sparse_average_linkage(
            len(d), ii, jj, dd, cutoff, keep
        )
        assert got_approx == want_approx
        assert np.array_equal(got_labels, want_labels)


def test_native_sparse_upgma_duplicate_edges(rng, monkeypatch):
    """Duplicate input edges collapse to their min identically in both
    implementations (first-writer-wins on exact ties)."""
    import drep_tpu.native as native_mod
    from drep_tpu.ops.linkage import sparse_average_linkage

    if native_mod.get_library() is None:
        import pytest

        pytest.skip("no compiler: native path unavailable")
    d = _blocky_dist(rng, [4, 6, 3])
    ii, jj, dd = _edges_below(d, keep=0.3)
    # duplicate every edge with jitter, and append exact-tie duplicates
    ii2 = np.concatenate([ii, jj, ii])
    jj2 = np.concatenate([jj, ii, jj])
    dd2 = np.concatenate([dd, dd + 0.01, dd])
    want = _python_sparse_upgma(len(d), ii2, jj2, dd2, 0.10, 0.3, monkeypatch)
    got = sparse_average_linkage(len(d), ii2, jj2, dd2, 0.10, 0.3)
    assert got[1] == want[1]
    assert np.array_equal(got[0], want[0])


def test_native_sparse_upgma_rejects_out_of_range(rng):
    """An out-of-range edge index is a caller bug: loud on the native path
    (the python reference would KeyError), never a silent wrong partition."""
    import pytest

    import drep_tpu.native as native_mod
    from drep_tpu.ops.linkage import sparse_average_linkage

    if native_mod.get_library() is None:
        pytest.skip("no compiler: native path unavailable")
    with pytest.raises(ValueError, match="out of range"):
        sparse_average_linkage(
            4, np.array([0, 4]), np.array([1, 2]), np.array([0.05, 0.05]), 0.1, 0.25
        )


# ---- the dense primary's flat clusters by component (ISSUE 37) ---------------


def _accounted(did: dict) -> bool:
    """Every component is a singleton, a clique or a linkage call."""
    return did["singletons"] + did["cliques"] + did["linkage_calls"] == did["components"]


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("method", ["average", "single", "complete", "weighted", "ward"])
@pytest.mark.parametrize("cutoff", [0.02, 0.1, 0.3, 0.7])
def test_components_equal_the_whole_tree_without_ties(dtype, method, cutoff):
    """(a) random matrices, every distance distinct: the component route's
    labels are the labels the whole tree is cut to."""
    rng = np.random.default_rng([int(cutoff * 100), len(method)])
    for n in (3, 17, 120):
        d = _random_dist(rng, n).astype(dtype)
        # skew towards the small distances, so that every cutoff cuts something
        d = d ** 3
        got, did = cluster_by_components(d, cutoff, method=method)
        want, _ = cluster_hierarchical(d, cutoff, method=method)
        assert np.array_equal(got, want), (n, did)
        assert did["genomes"] == n and _accounted(did)
        if method == "ward":
            assert did["components"] == 1 and did["rows_linked"] == n  # not a component method


def _planted_representatives(rng, n: int, dtype=np.float32) -> np.ndarray:
    """`gtdb_reps_10k`'s shape: 85% singletons, the rest congeners in groups
    of 2-4 at Mash ~0.04, everything else far over the cutoff."""
    d = rng.uniform(0.3, 0.9, size=(n, n))
    d = np.minimum(d, d.T)
    perm = rng.permutation(n)
    at = int(n * 0.85)
    while at < n:
        size = min(int(rng.integers(2, 5)), n - at)
        members = perm[at : at + size]
        block = rng.uniform(0.03, 0.05, size=(size, size))
        d[np.ix_(members, members)] = np.minimum(block, block.T)
        at += size
    np.fill_diagonal(d, 0.0)
    return d.astype(dtype)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_components_settle_planted_representatives_without_scipy(seed):
    """(b) a catalogue of species representatives at n = 2,000: the labels of
    the whole tree, and not one linkage call."""
    d = _planted_representatives(np.random.default_rng(seed), 2000)
    got, did = cluster_by_components(d, 1.0 - 0.9)
    want, _ = cluster_hierarchical(d, 1.0 - 0.9)
    assert np.array_equal(got, want)
    assert did["linkage_calls"] == 0 and did["rows_linked"] == 0
    assert did["components"] == int(got.max()) and did["largest"] <= 4
    assert did["singletons"] >= 1700 and _accounted(did)


def _chain() -> np.ndarray:
    # A-B and B-C under 0.1, A-C far over it; D alone; E-F a clique
    d = np.full((6, 6), 0.8)
    d[0, 1] = d[1, 0] = 0.04
    d[1, 2] = d[2, 1] = 0.06
    d[0, 2] = d[2, 0] = 0.5
    d[4, 5] = d[5, 4] = 0.02
    np.fill_diagonal(d, 0.0)
    return d


def _one_way() -> np.ndarray:
    # 0 -> 1 under the cutoff, 1 -> 0 over it: the maximum decides, no edge
    d = np.full((3, 3), 0.8)
    d[0, 1], d[1, 0] = 0.04, 0.4
    d[1, 2] = d[2, 1] = 0.05
    np.fill_diagonal(d, 0.0)
    return d


@pytest.mark.parametrize(
    "make, labels, did",
    [
        # (c) the loose chain goes through scipy and splits as the whole run does
        (_chain, [1, 1, 2, 3, 4, 4],
         {"components": 3, "singletons": 1, "cliques": 1, "linkage_calls": 1, "rows_linked": 3, "largest": 3}),
        # (d) one direction under, one over the cutoff
        (_one_way, [1, 2, 2],
         {"components": 2, "singletons": 1, "cliques": 1, "linkage_calls": 0, "rows_linked": 0, "largest": 2}),
        # (f) one genome, two near, two far
        (lambda: np.zeros((1, 1)), [1],
         {"components": 1, "singletons": 1, "cliques": 0, "linkage_calls": 0, "rows_linked": 0, "largest": 1}),
        (lambda: np.array([[0.0, 0.05], [0.05, 0.0]]), [1, 1],
         {"components": 1, "singletons": 0, "cliques": 1, "linkage_calls": 0, "rows_linked": 0, "largest": 2}),
        (lambda: np.array([[0.0, 0.5], [0.5, 0.0]]), [1, 2],
         {"components": 2, "singletons": 2, "cliques": 0, "linkage_calls": 0, "rows_linked": 0, "largest": 1}),
    ],
    ids=["loose_chain", "one_way_pair", "n1", "n2_near", "n2_far"],
)
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_components_small_cases(make, labels, did, dtype):
    d = make().astype(dtype)
    got, counted = cluster_by_components(d, 0.1)
    assert got.tolist() == labels
    assert got.tolist() == cluster_hierarchical(d, 0.1)[0].tolist()
    assert counted == {"genomes": len(labels), **did}


def test_cutoff_is_compared_as_float64_would():
    """1 - 0.9 is a hair under float32's 0.1: a float32 distance of 0.1 is
    over the cutoff for the whole tree, and is for the component route."""
    d = np.array([[0.0, 0.1], [0.1, 0.0]], dtype=np.float32)
    assert cluster_hierarchical(d, 1.0 - 0.9)[0].tolist() == [1, 2]
    assert cluster_by_components(d, 1.0 - 0.9)[0].tolist() == [1, 2]
    assert cluster_by_components(d, 0.2)[0].tolist() == [1, 1]


def _mash_from_counts(rng, n: int, sketch: int, lo: int, hi: int, unshared: float, k: int = 21):
    """Mash distances of integer shared counts `lo..hi` of `sketch`, as the
    primary's are, with the cutoff of 0.1 in the middle of them; a share
    `unshared` of the pairs has nothing in common. Exact ties everywhere."""
    shared = rng.integers(lo, hi + 1, size=(n, n))
    shared[rng.random((n, n)) < unshared] = 0
    shared = np.minimum(shared, shared.T)
    j = shared / (2.0 * sketch - shared)
    with np.errstate(divide="ignore"):
        d = np.where(shared > 0, -np.log(2.0 * j / (1.0 + j)) / k, 1.0)
    np.fill_diagonal(d, 0.0)
    return d.astype(np.float32)


def _is_an_average_linkage_cut(d: np.ndarray, labels: np.ndarray, cutoff: float) -> bool:
    """Every cluster merges wholly at or under the cutoff on its own, and no
    two clusters lie at an average at or under it."""
    d = np.asarray(d, dtype=np.float64)
    groups = [np.flatnonzero(labels == c) for c in np.unique(labels)]
    for g in groups:
        if len(g) > 1:
            link = sch.linkage(ssd.squareform(d[np.ix_(g, g)], checks=False), method="average")
            if link[:, 2].max() > cutoff:
                return False
    return all(
        d[np.ix_(a, b)].mean() > cutoff for i, a in enumerate(groups) for b in groups[i + 1 :]
    )


TIE_SEEDS = 200


@pytest.mark.parametrize(
    "sketch, lo, hi, differing",
    # what PARITY.md quotes ("Dense primary linkage by component"): the seeds
    # of TIE_SEEDS whose partition is another than the whole tree's
    [(24, 1, 5, 103), (1000, 80, 200, 6)],
    ids=["five_values", "sketch_1000"],
)
def test_components_under_exact_ties_give_an_average_linkage_cut(sketch, lo, hi, differing):
    """(e) loose components of 60 genomes whose distances tie exactly: scipy's
    order among tied merges on a submatrix may be another than on the whole
    matrix, and a tie that straddles the cutoff then cuts differently. Every
    such partition is still a cut average linkage could have made; with the
    ties broken by 1e-7 of noise no seed differs."""
    cutoff = 1.0 - 0.9
    differ = []
    for seed in range(TIE_SEEDS):
        rng = np.random.default_rng(seed)
        d = _mash_from_counts(rng, 60, sketch, lo, hi, unshared=0.7)
        got, did = cluster_by_components(d, cutoff)
        assert _accounted(did) and did["linkage_calls"] >= 1
        want, _ = cluster_hierarchical(d, cutoff)
        if not np.array_equal(got, want):
            differ.append(seed)
            assert _is_an_average_linkage_cut(d, got, cutoff), seed
            assert _is_an_average_linkage_cut(d, want, cutoff), seed
        noise = rng.random(d.shape) * 1e-7
        untied = d.astype(np.float64) + np.triu(noise, 1) + np.triu(noise, 1).T
        assert np.array_equal(
            cluster_by_components(untied, cutoff)[0], cluster_hierarchical(untied, cutoff)[0]
        ), seed
    print(f"partitions other than the whole tree's: {len(differ)} of {TIE_SEEDS} seeds: {differ}")
    assert len(differ) == differing

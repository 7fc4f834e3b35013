"""The plain reference against answers known by construction."""

import numpy as np
import pytest

from benchmark import reference as ref


def test_candidate_pairs_are_exactly_the_pairs_that_share_a_hash():
    rng = np.random.default_rng(0)
    sketches = [np.unique(rng.integers(0, 400, size=30).astype(np.uint64)) for _ in range(25)]
    want = {(i, j) for i in range(25) for j in range(i + 1, 25)
            if len(np.intersect1d(sketches[i], sketches[j]))}
    assert {tuple(p) for p in ref.candidate_pairs(sketches, 1000).tolist()} == want


def test_mash_jaccard_is_over_the_bottom_of_the_union():
    a = np.array([1, 2, 3, 10], np.uint64)
    b = np.array([2, 3, 4, 5], np.uint64)
    # union bottom-4 = {1,2,3,4}; shared among them = {2,3}
    assert ref.mash_jaccard(a, b, 4) == 0.5
    assert ref.mash_distance(0.0, 21) == 1.0 and ref.mash_distance(1.0, 21) == 0.0


def test_bfloat16_rounding():
    assert ref.to_bfloat16(1.0) == 1.0
    assert ref.to_bfloat16(0.01) == pytest.approx(0.01, rel=2 ** -8)
    assert ref.to_bfloat16(0.01) != 0.01


def test_partition_mismatch_counts_genomes():
    a = {frozenset({1, 2}), frozenset({3})}
    assert ref.partition_mismatch(a, a) == 0
    assert ref.partition_mismatch({frozenset({1, 2, 3})}, a) == 3

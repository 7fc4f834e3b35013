"""Containment-ANI engines: numpy oracle, matmul/searchsorted equivalence,
and the mutation-rate accuracy contract (ANI ~ 1 - p)."""

import numpy as np
import pytest

from drep_tpu.ops import kmers
from drep_tpu.ops.containment import (
    all_vs_all_containment,
    all_vs_all_containment_matmul,
    pack_scaled_sketches,
)


def oracle_containment(a: np.ndarray, b: np.ndarray) -> float:
    a_set, b_set = set(a.tolist()), set(b.tolist())
    return len(a_set & b_set) / max(len(a_set), 1)


def _sketches(rng, n=8, size=400, overlap=0.5):
    pool = np.unique(rng.integers(0, 2**40, size=8 * size * n, dtype=np.uint64))
    rng.shuffle(pool)
    shared = pool[:size]
    out = []
    for i in range(n):
        own = pool[size * (i + 1) : size * (i + 2)]
        take = int(size * overlap * rng.random())
        out.append(np.sort(np.unique(np.concatenate([shared[:take], own[: size - take]]))))
    return out


def test_searchsorted_matches_oracle(rng):
    sketches = _sketches(rng)
    packed = pack_scaled_sketches(sketches, [f"g{i}" for i in range(len(sketches))], pad_multiple=32)
    ani, cov = all_vs_all_containment(packed, k=21, tile=8)
    for i in range(len(sketches)):
        for j in range(len(sketches)):
            want_cov = 1.0 if i == j else oracle_containment(sketches[i], sketches[j])
            assert abs(cov[i, j] - want_cov) < 1e-6, (i, j)
            cmax = max(want_cov, 1.0 if i == j else oracle_containment(sketches[j], sketches[i]))
            want_ani = 1.0 if i == j else (cmax ** (1 / 21) if cmax > 0 else 0.0)
            assert abs(ani[i, j] - want_ani) < 1e-5
    np.testing.assert_array_equal(ani, ani.T)  # max-containment ANI is symmetric


def test_matmul_path_equals_searchsorted(rng):
    sketches = _sketches(rng, n=13, size=300)
    packed = pack_scaled_sketches(sketches, [f"g{i}" for i in range(13)], pad_multiple=32)
    a1, c1 = all_vs_all_containment(packed, k=21, tile=8)
    a2, c2 = all_vs_all_containment_matmul(packed, k=21)
    assert np.abs(a1 - a2).max() < 1e-6
    assert np.abs(c1 - c2).max() < 1e-6


def test_ani_tracks_mutation_rate(rng):
    """End-to-end numeric contract: a genome mutated at rate p must measure
    ANI ~ 1-p through the full kmer->scaled-sketch->containment stack."""
    bases = np.frombuffer(b"ACGT", dtype=np.uint8)
    seq = bases[rng.integers(0, 4, size=200_000)]
    for p in (0.01, 0.03, 0.05):
        mut = seq.copy()
        pos = np.nonzero(rng.random(len(seq)) < p)[0]
        mut[pos] = bases[(np.searchsorted(bases, mut[pos]) + rng.integers(1, 4, len(pos))) % 4]
        h1 = kmers.scaled_sketch(kmers.kmer_hashes(seq.tobytes(), 21), scale=50)
        h2 = kmers.scaled_sketch(kmers.kmer_hashes(mut.tobytes(), 21), scale=50)
        packed = pack_scaled_sketches([h1, h2], ["a", "b"], pad_multiple=128)
        ani, cov = all_vs_all_containment_matmul(packed, k=21)
        measured = (ani[0, 1] + ani[1, 0]) / 2
        assert abs(measured - (1 - p)) < 0.004, (p, measured)


def test_size_asymmetry_uses_max_containment(rng):
    """A genome CONTAINED in a twice-larger one (plus 1% divergence) must
    measure ANI ~0.99 — not the size-ratio-diluted value the mean of the
    two containments would give. This is the fastANI-divergence regime the
    max-containment transform exists for (fragment-identity ANI ignores
    the larger genome's extra content; so must we)."""
    bases = np.frombuffer(b"ACGT", dtype=np.uint8)
    small = bases[rng.integers(0, 4, size=150_000)]
    extra = bases[rng.integers(0, 4, size=150_000)]
    mut = small.copy()
    pos = np.nonzero(rng.random(len(small)) < 0.01)[0]
    mut[pos] = bases[(np.searchsorted(bases, mut[pos]) + rng.integers(1, 4, len(pos))) % 4]
    big = np.concatenate([mut, extra])
    h_small = kmers.scaled_sketch(kmers.kmer_hashes(small.tobytes(), 21), scale=50)
    h_big = kmers.scaled_sketch(kmers.kmer_hashes(big.tobytes(), 21), scale=50)
    packed = pack_scaled_sketches([h_small, h_big], ["small", "big"], pad_multiple=128)
    ani, cov = all_vs_all_containment_matmul(packed, k=21)
    assert ani[0, 1] == ani[1, 0]
    assert abs(ani[0, 1] - 0.99) < 0.004, ani[0, 1]
    # the coverages stay directional: the big genome is only half-covered
    assert cov[0, 1] > 0.7 and cov[1, 0] < 0.55, (cov[0, 1], cov[1, 0])


def test_empty_sketch_row(rng):
    sketches = _sketches(rng, n=3)
    sketches.append(np.empty(0, dtype=np.uint64))
    packed = pack_scaled_sketches(sketches, ["a", "b", "c", "empty"], pad_multiple=32)
    ani, cov = all_vs_all_containment(packed, k=21, tile=4)
    assert cov[3, 0] == 0.0 and ani[3, 0] == 0.0

    a2, c2 = all_vs_all_containment_matmul(packed, k=21)
    assert c2[3, 0] == 0.0 and a2[3, 0] == 0.0


def test_indicator_dtype_paths_bit_identical(rng, monkeypatch):
    """The two indicator dtypes (int8 — the production choice on every
    backend — and the float32 experiment override, see _indicator_dtype)
    must produce IDENTICAL int32 counts. Covers the self matmul, the
    vocab-chunked path, and the rectangular kernel the greedy route
    uses."""
    from drep_tpu.ops.containment import (
        all_vs_all_containment_matmul_chunked,
        intersect_counts_matmul_rect,
    )

    sketches = _sketches(rng, n=11, size=350)
    packed = pack_scaled_sketches(sketches, [f"g{i}" for i in range(11)], pad_multiple=32)
    out = {}
    for dt in ("int8", "float32"):
        monkeypatch.setenv("DREP_TPU_INDICATOR_DTYPE", dt)
        ani_s, cov_s = all_vs_all_containment_matmul(packed, k=21)
        ani_c, cov_c = all_vs_all_containment_matmul_chunked(packed, k=21)
        rect = intersect_counts_matmul_rect(packed.ids[:5], packed.ids[5:])
        out[dt] = (ani_s, cov_s, ani_c, cov_c, rect)
        assert rect.dtype == np.int32
    for a, b in zip(out["int8"], out["float32"]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("case", ["ragged", "with_empty_rows"])
def test_shared_vocabulary_pack_equals_double_loop_oracle(rng, case):
    """`pack_scaled_sketches` fills its matrix through the same row-slice
    helper as the cluster-local pack (ISSUE 25): ids (values, dtype, pad
    value, width) and counts against np.unique of all hashes and a
    per-element np.searchsorted, a plain double loop."""
    from drep_tpu.ops.minhash import PAD_ID

    sketches = _sketches(rng, n=7, size=300)
    if case == "with_empty_rows":
        empty = np.empty(0, dtype=np.uint64)
        sketches = [empty, sketches[0], empty, *sketches[1:], empty]
    names = [f"g{i}" for i in range(len(sketches))]
    packed = pack_scaled_sketches(sketches, names, pad_multiple=32)

    vocab = np.unique(np.concatenate(sketches))
    longest = max(len(s) for s in sketches)
    want = np.full((len(sketches), max(32, 1 << (longest - 1).bit_length())), PAD_ID, np.int32)
    for r, s in enumerate(sketches):
        for j, h in enumerate(s):
            want[r, j] = np.searchsorted(vocab, h)
    assert packed.ids.dtype == np.int32 and packed.ids.shape == want.shape
    assert packed.ids.tobytes() == want.tobytes()
    assert packed.counts.dtype == np.int32
    assert packed.counts.tolist() == [len(s) for s in sketches]
    assert packed.names == names


# ---- ISSUE 44: the shared-vocabulary pack ranks in native/rank.cc ----------------


def _shared_by_16(rng):
    # 4 groups of 16 rows; every hash of a group is in all 16 of its rows
    groups = [np.unique(rng.integers(0, 2**64 // 1000, size=300, dtype=np.uint64)) for _ in range(4)]
    return [g.copy() for g in groups for _ in range(16)]


def _disjoint(rng):
    pool = np.unique(rng.integers(0, 2**63, size=4000, dtype=np.uint64))[:3000]
    rng.shuffle(pool)
    return [np.sort(pool[i * 250 : (i + 1) * 250]) for i in range(12)]


_SCALED_CASES = {
    "every_hash_shared_by_16_rows": _shared_by_16,
    "disjoint_rows": _disjoint,
    "one_row": lambda rng: [np.unique(rng.integers(0, 2**64 - 1, size=500, dtype=np.uint64))],
    "an_empty_row_between_full_ones": lambda rng: [
        *_sketches(rng, n=2, size=200), np.empty(0, np.uint64), *_sketches(rng, n=2, size=200)],
    # 255, 256 and 257 hashes: the longest row sets the pow2 width, 512
    "lengths_that_cross_a_pow2_width": lambda rng: [
        np.unique(rng.integers(0, 2**50, size=4 * n, dtype=np.uint64))[:n] for n in (255, 256, 257, 31)],
}
# how the ranks are computed: NumPy's lines (no library), or native/rank.cc on so many threads
_SCALED_PATHS = {"numpy": None, "native_x1": 1, "native_x2": 2, "native_x6": 6}


@pytest.fixture(params=list(_SCALED_PATHS))
def scaled_pack_workers(request, monkeypatch):
    """`workers` for `pack_scaled_sketches`, with the path it names made the
    one that serves: no library for NumPy, six usable cores for native."""
    from drep_tpu import native
    from drep_tpu.ops import minhash

    workers = _SCALED_PATHS[request.param]
    if workers is None:
        monkeypatch.setattr(native, "get_library", lambda: None)
        assert minhash.rank_route(10, 6) == ("numpy", 1)
        return 6
    if native.get_library() is None:
        pytest.skip("native library unavailable (no g++?)")
    monkeypatch.setattr(minhash, "_usable_cores", lambda: 6)
    monkeypatch.setattr(minhash, "RANK_HASHES_PER_THREAD", 1)  # the toy cases on every thread asked for
    assert minhash.rank_route(10, workers) == ("native", workers)
    return workers


@pytest.mark.parametrize("case", list(_SCALED_CASES))
def test_shared_vocabulary_pack_is_byte_equal_to_numpys_by_either_route(case, scaled_pack_workers):
    """ISSUE 44: the ranks of `pack_scaled_sketches` come from the primary
    pack's kernel at the caller's thread width, or from NumPy's lines where
    there is no library: the same bytes as `np.unique` over all rows and a
    search of each row into it."""
    from drep_tpu.ops.minhash import PAD_ID

    sketches = _SCALED_CASES[case](np.random.default_rng(44))
    names = [f"g{i}" for i in range(len(sketches))]
    vocab = np.unique(np.concatenate(sketches))
    longest = max(len(s) for s in sketches)
    want = np.full((len(sketches), max(32, 1 << (longest - 1).bit_length())), PAD_ID, np.int32)
    for r, s in enumerate(sketches):
        want[r, : len(s)] = np.searchsorted(vocab, s)
    packed = pack_scaled_sketches(sketches, names, pad_multiple=32, workers=scaled_pack_workers)
    assert packed.ids.dtype == np.int32 and packed.counts.dtype == np.int32
    assert packed.ids.shape == want.shape and packed.ids.flags.c_contiguous
    assert packed.ids.tobytes() == want.tobytes()
    assert packed.counts.tolist() == [len(s) for s in sketches]
    assert packed.names == names and packed.names is not names
    assert [s.dtype for s in sketches] == [np.uint64] * len(sketches)  # read where they lie, unchanged


def test_shared_vocabulary_pack_refuses_a_vocabulary_at_the_limit_by_either_route(
        monkeypatch, scaled_pack_workers):
    """The vocabulary's size is checked before a rank is an int32: in
    NumPy's lines, and as the limit the native kernel is handed."""
    from drep_tpu.ops.minhash import PAD_ID

    monkeypatch.setattr(np, "iinfo", lambda dtype: type("I", (), {"max": 5})())
    sk = [np.arange(3, dtype=np.uint64), np.arange(2, 5, dtype=np.uint64)]
    with pytest.raises(ValueError, match="id space overflow: >2\\^31 distinct sketch hashes"):
        pack_scaled_sketches(sk, ["a", "b"], pad_multiple=8, workers=scaled_pack_workers)
    under = pack_scaled_sketches([sk[0], sk[0] + 1], ["a", "b"], pad_multiple=8,
                                 workers=scaled_pack_workers)
    assert under.ids[:, :3].tolist() == [[0, 1, 2], [1, 2, 3]] and (under.ids[:, 3:] == PAD_ID).all()

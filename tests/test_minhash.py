"""Device MinHash estimator vs pure-Python Mash oracle."""

import math

import numpy as np
import pytest

from drep_tpu.ops import minhash


def oracle_mash(a: np.ndarray, b: np.ndarray, s: int, k: int) -> float:
    """Union-bottom-s Mash estimator on uint64 sketch values (slow, honest)."""
    a, b = set(a.tolist()), set(b.tolist())
    union = sorted(a | b)
    s_use = min(s, len(a), len(b))
    bottom = set(union[:s_use])
    shared = len(bottom & a & b)
    j = shared / s_use if s_use else 0.0
    if j == 0.0:
        return 1.0
    return min(1.0, max(0.0, -math.log(2 * j / (1 + j)) / k))


def _random_sketches(rng, n, s, overlap=0.5):
    base = np.unique(rng.integers(0, 2**62, size=4 * s * n, dtype=np.uint64))
    rng.shuffle(base)
    out = []
    shared_pool = base[: 2 * s]
    rest = base[2 * s :]
    for i in range(n):
        own = rest[i * s : (i + 1) * s]
        take = int(s * overlap)
        sk = np.unique(np.concatenate([shared_pool[:take], own[: s - take]]))[:s]
        out.append(np.sort(sk))
    return out


def test_tile_matches_oracle(rng):
    s = 64
    sketches = _random_sketches(rng, 6, s)
    names = [f"g{i}" for i in range(6)]
    packed = minhash.pack_sketches(sketches, names, s)
    dist, jac = minhash.all_vs_all_mash(packed, k=21, tile=4)
    for i in range(6):
        for j in range(6):
            want = 0.0 if i == j else oracle_mash(sketches[i], sketches[j], s, 21)
            assert abs(dist[i, j] - want) < 1e-5, (i, j, dist[i, j], want)


def test_identical_sketches_zero_distance(rng):
    s = 128
    sk = np.sort(np.unique(rng.integers(0, 2**62, 4 * s, dtype=np.uint64)))[:s]
    packed = minhash.pack_sketches([sk, sk.copy()], ["a", "b"], s)
    dist, jac = minhash.all_vs_all_mash(packed, k=21)
    assert dist[0, 1] == 0.0
    assert jac[0, 1] == 1.0


def test_disjoint_sketches_max_distance(rng):
    s = 64
    vals = np.unique(rng.integers(0, 2**62, 10 * s, dtype=np.uint64))
    a, b = np.sort(vals[:s]), np.sort(vals[s : 2 * s])
    packed = minhash.pack_sketches([a, b], ["a", "b"], s)
    dist, jac = minhash.all_vs_all_mash(packed, k=21)
    assert dist[0, 1] == 1.0
    assert jac[0, 1] == 0.0


def test_ragged_sketch_counts(rng):
    """A genome with fewer than s k-mers still estimates correctly."""
    s = 64
    vals = np.unique(rng.integers(0, 2**62, 10 * s, dtype=np.uint64))
    a = np.sort(vals[: s // 2])  # small genome
    b = np.sort(np.concatenate([a, vals[s : s + s // 2]]))[:s]
    packed = minhash.pack_sketches([a, b], ["a", "b"], s)
    dist, _ = minhash.all_vs_all_mash(packed, k=21)
    want = oracle_mash(a, b, s, 21)
    assert abs(dist[0, 1] - want) < 1e-5


def test_padding_tiles_beyond_n(rng):
    """N not divisible by tile: padded rows must not perturb real entries."""
    s = 32
    sketches = _random_sketches(rng, 5, s)
    packed = minhash.pack_sketches(sketches, [f"g{i}" for i in range(5)], s)
    d1, _ = minhash.all_vs_all_mash(packed, k=21, tile=4)
    d2, _ = minhash.all_vs_all_mash(packed, k=21, tile=8)
    assert np.allclose(d1, d2, atol=1e-6)


def test_mash_distance_formula():
    import jax.numpy as jnp

    j = jnp.array([1.0, 0.5, 0.0])
    d = np.asarray(minhash.mash_distance_from_jaccard(j, 21))
    assert d[0] == 0.0
    assert d[2] == 1.0
    assert abs(d[1] - (-math.log(2 * 0.5 / 1.5) / 21)) < 1e-5  # float32 tolerance


def _pack_by_search(sketches, names, sketch_size):
    """The oracle: the spelling `pack_sketches` had before ISSUE 28, one
    `np.unique` vocabulary and a `searchsorted` per row."""
    trimmed = [np.asarray(s)[:sketch_size] for s in sketches]
    vocab = np.unique(np.concatenate(trimmed)) if trimmed else np.empty(0, np.uint64)
    ids = np.full((len(trimmed), sketch_size), minhash.PAD_ID, dtype=np.int32)
    for row, s in zip(ids, trimmed):
        row[: len(s)] = np.searchsorted(vocab, s)
    return ids, np.array([len(s) for s in trimmed], dtype=np.int32)


def _hashes(rng, n):
    return np.unique(rng.integers(0, 2**64, size=n, dtype=np.uint64))


def _shared(rng, rows, take, extra=0, pool=600):
    """`rows` sketches that draw `take` hashes each from one pool (and
    `extra` of their own): hashes shared across rows."""
    pool = _hashes(rng, pool)
    return [np.unique(np.concatenate([rng.choice(pool, size=take, replace=False), _hashes(rng, extra)]))
            for _ in range(rows)]


def _under(rng, n, top):
    return np.unique(rng.integers(0, top, size=n, dtype=np.uint64))


# name -> (rng -> sketches, sketch_size); built inside its own case only
_PACK_CASES = {
    "all_rows_full": (lambda rng: [_hashes(rng, 80)[:64] for _ in range(9)], 64),
    "ragged_rows": (lambda rng: [_hashes(rng, n) for n in (64, 1, 0, 37, 63, 0, 12)], 64),
    "longer_than_sketch_size": (lambda rng: [_hashes(rng, n) for n in (200, 64, 65, 10, 500)], 64),
    "hashes_shared_across_rows": (lambda rng: _shared(rng, 12, 50), 64),
    "one_hash_in_every_row": (
        lambda rng: [np.unique(np.append(_hashes(rng, 30), np.uint64(2**63 + 12345))) for _ in range(8)],
        32),
    "one_genome": (lambda rng: [_hashes(rng, 40)], 64),
    "no_genome": (lambda rng: [], 64),
    "rows_2000_of_1000": (lambda rng: [s[:1000] for s in _shared(rng, 2000, 300, extra=800)], 1000),
    # ISSUE 40, the kernel's buckets: 30,000 hashes are cut into 8 by their
    # top three bits under the largest
    "every_hash_in_one_bucket": (lambda rng: [np.arange(7, 607, dtype=np.uint64) + 2**40] * 50, 600),
    "hashes_crowd_the_low_buckets": (
        lambda rng: [_under(rng, 700, 2**57 if i % 3 else 2**57 // 100)[:600] for i in range(60)], 600),
    "fewer_hashes_than_threads": (lambda rng: [_hashes(rng, 2), _hashes(rng, 0), _hashes(rng, 1)], 4),
}

# how the ranks are computed: NumPy's lines, or native/rank.cc on so many threads
_PACK_PATHS = {"numpy": None, "native_x1": 1, "native_x2": 2, "native_x6": 6}


@pytest.fixture(params=list(_PACK_PATHS))
def pack_workers(request, monkeypatch):
    """`workers` for `pack_sketches`, with the path it names made the one
    that serves: the kill switch for NumPy, six usable cores for native."""
    from drep_tpu import native

    workers = _PACK_PATHS[request.param]
    if workers is None:
        monkeypatch.setenv("DREP_TPU_NO_NATIVE", "1")
        assert minhash.rank_route(10, 6) == ("numpy", 1)
        return 6
    if native.get_library() is None:
        pytest.skip("native library unavailable (no g++?)")
    monkeypatch.setattr(minhash, "_usable_cores", lambda: 6)
    monkeypatch.setattr(minhash, "RANK_HASHES_PER_THREAD", 1)  # the toy cases on every thread asked for
    assert minhash.rank_route(10, workers) == ("native", workers)
    return workers


@pytest.mark.parametrize("case", list(_PACK_CASES))
def test_pack_sketches_is_byte_equal_to_the_searching_spelling(case, pack_workers):
    """ISSUE 28: ranks from one sort and a running count of run starts are
    the ranks a binary search into the sorted vocabulary finds. ISSUE 40:
    so are the native kernel's, at every thread width."""
    build, sketch_size = _PACK_CASES[case]
    sketches = build(np.random.default_rng(28))
    names = [f"g{i}" for i in range(len(sketches))]
    want_ids, want_counts = _pack_by_search(sketches, names, sketch_size)
    packed = minhash.pack_sketches(sketches, names, sketch_size, workers=pack_workers)
    assert packed.ids.dtype == np.int32 and packed.counts.dtype == np.int32
    assert packed.ids.shape == want_ids.shape == (len(sketches), sketch_size)
    assert packed.ids.tobytes() == want_ids.tobytes()
    assert packed.counts.tobytes() == want_counts.tobytes()
    assert packed.names == names and packed.names is not names


def test_pack_sketches_refuses_a_vocabulary_beyond_int32(monkeypatch, pack_workers):
    """The check reads the vocabulary's size before a rank is an int32: in
    NumPy's lines, and as the limit the native kernel is handed."""
    monkeypatch.setattr(minhash.np, "iinfo", lambda dtype: type("I", (), {"max": 5})())
    sk = [np.arange(3, dtype=np.uint64), np.arange(2, 5, dtype=np.uint64)]
    with pytest.raises(ValueError, match="id space overflow"):
        minhash.pack_sketches(sk, ["a", "b"], 8, workers=pack_workers)
    assert minhash.pack_sketches([sk[0], sk[0] + 1], ["a", "b"], 8, workers=pack_workers).ids.max() == minhash.PAD_ID


def test_native_rank_kernel_says_overflow_by_its_return_and_writes_no_rank():
    """The kernel counts the vocabulary before it writes: at the limit it
    returns the count with the matrix holding no rank, under it the ranks."""
    from drep_tpu import native

    if native.get_library() is None:
        pytest.skip("native library unavailable (no g++?)")
    rows = [np.array([9, 4, 4], np.uint64), np.array([2**64 - 1, 9], np.uint64)]  # unsorted, repeated
    out = np.full((2, 4), -7, np.int32)
    assert native.rank_rows_native(rows, out, pad=-1, threads=2, limit=3) == 3
    assert not np.isin(out, [0, 1, 2]).any()
    assert native.rank_rows_native(rows, out, pad=-1, threads=2, limit=4) == 3
    assert out.tolist() == [[1, 0, 0, -1], [2, 1, -1, -1]]


def test_rank_route_is_the_workers_capped_by_the_cores_and_the_hashes(monkeypatch):
    from drep_tpu import native

    if native.get_library() is None:
        pytest.skip("native library unavailable (no g++?)")
    monkeypatch.setattr(minhash, "_usable_cores", lambda: 4)
    assert [minhash.rank_route(10**6, w) for w in (0, 1, 3, 6)] == [
        ("native", 1), ("native", 1), ("native", 3), ("native", 4)]
    # a thread needs RANK_HASHES_PER_THREAD hashes to itself: few hashes, few threads
    per = minhash.RANK_HASHES_PER_THREAD
    assert [minhash.rank_route(h, 6)[1] for h in (1, per, 2 * per - 1, 2 * per, 3 * per, 10 * per)] == [
        1, 1, 1, 2, 3, 4]
    assert minhash.rank_route(0, 6) == ("numpy", 1)  # nothing to rank

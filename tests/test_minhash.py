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
}


@pytest.mark.parametrize("case", list(_PACK_CASES))
def test_pack_sketches_is_byte_equal_to_the_searching_spelling(case):
    """ISSUE 28: ranks from one sort and a running count of run starts are
    the ranks a binary search into the sorted vocabulary finds."""
    build, sketch_size = _PACK_CASES[case]
    sketches = build(np.random.default_rng(28))
    names = [f"g{i}" for i in range(len(sketches))]
    want_ids, want_counts = _pack_by_search(sketches, names, sketch_size)
    packed = minhash.pack_sketches(sketches, names, sketch_size)
    assert packed.ids.dtype == np.int32 and packed.counts.dtype == np.int32
    assert packed.ids.shape == want_ids.shape == (len(sketches), sketch_size)
    assert packed.ids.tobytes() == want_ids.tobytes()
    assert packed.counts.tobytes() == want_counts.tobytes()
    assert packed.names == names and packed.names is not names


def test_pack_sketches_refuses_a_vocabulary_beyond_int32(monkeypatch):
    """The check reads the vocabulary's size before a rank is an int32."""
    monkeypatch.setattr(minhash.np, "iinfo", lambda dtype: type("I", (), {"max": 5})())
    sk = [np.arange(3, dtype=np.uint64), np.arange(2, 5, dtype=np.uint64)]
    with pytest.raises(ValueError, match="id space overflow"):
        minhash.pack_sketches(sk, ["a", "b"], 8)
    assert minhash.pack_sketches([sk[0], sk[0] + 1], ["a", "b"], 8).ids.max() == minhash.PAD_ID

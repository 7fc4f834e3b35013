"""Pallas union-bottom-s Mash kernel vs the jnp reference estimator.

Exact equality is the contract: the kernel implements the SAME estimator
(shared-within-bottom-s_use-of-union), so `shared` counts — and hence
distances — must be bit-identical to ops/minhash.py::mash_distance_tile.
CPU runs use interpret mode (SURVEY.md §4 rebuild note); the compiled
kernel's jobs are checked against the plain reference on the chip by
benchmark/. The jnp bitonic merge both formulations share (ops/merge.py)
is pinned against a full sort here too.
"""

import numpy as np
import pytest

from drep_tpu.ops.minhash import PAD_ID, mash_distance_tile, pack_sketches
from drep_tpu.ops.pallas_mash import mash_distance_tile_pallas


def _sketch_set(rng, n, s, overlap=0.6):
    base = np.unique(rng.integers(0, 2**62, size=8 * s * n, dtype=np.uint64))
    rng.shuffle(base)
    shared = base[:s]
    out = []
    for i in range(n):
        own = base[s * (i + 1) : s * (i + 2)]
        mix = int(s * overlap * rng.random())
        out.append(np.sort(np.unique(np.concatenate([shared[:mix], own[: s - mix]]))[:s]))
    return out


@pytest.mark.parametrize("n,s", [(12, 64), (9, 100)])
def test_pallas_mash_equals_jnp_tile(rng, n, s):
    packed = pack_sketches(_sketch_set(rng, n, s), [f"g{i}" for i in range(n)], s)
    want_d, want_j = mash_distance_tile(
        packed.ids, packed.counts, packed.ids, packed.counts, k=21
    )
    got_d, got_j = mash_distance_tile_pallas(
        packed.ids, packed.counts, packed.ids, packed.counts, k=21
    )
    np.testing.assert_allclose(got_j, np.asarray(want_j), atol=0)  # exact
    np.testing.assert_allclose(got_d, np.asarray(want_d), atol=1e-7)


def test_pallas_mash_ragged_counts(rng):
    """Short rows (counts < width) change s_use per pair — the kernel must
    honor min(|A|, |B|, s) exactly, including zero-count padded rows."""
    s = 64
    sketches = _sketch_set(rng, 6, s)
    sketches[2] = sketches[2][: s // 3]
    sketches[4] = sketches[4][: s // 2]
    packed = pack_sketches(sketches, [f"g{i}" for i in range(6)], s)
    assert packed.counts.min() < s  # genuinely ragged
    want_d, _ = mash_distance_tile(
        packed.ids, packed.counts, packed.ids, packed.counts, k=21
    )
    got_d, _ = mash_distance_tile_pallas(
        packed.ids, packed.counts, packed.ids, packed.counts, k=21
    )
    np.testing.assert_allclose(got_d, np.asarray(want_d), atol=1e-7)


def test_all_vs_all_pallas_symmetric_grid(rng):
    """The wrapped half-grid full-matrix path must equal the plain tiled
    all-vs-all (same estimator, ~2x less kernel work)."""
    from drep_tpu.ops.minhash import all_vs_all_mash
    from drep_tpu.ops.pallas_mash import all_vs_all_mash_pallas

    n, s = 10, 64
    packed = pack_sketches(_sketch_set(rng, n, s), [f"g{i}" for i in range(n)], s)
    want_d, want_j = all_vs_all_mash(packed, k=21, tile=8)
    got_d, got_j = all_vs_all_mash_pallas(packed, k=21, jaccard=True)
    np.testing.assert_allclose(got_d, want_d, atol=1e-7)
    np.testing.assert_allclose(got_j, want_j, atol=1e-7)


def _unwrap_symmetric(compact: np.ndarray, tile: int) -> np.ndarray:
    """[na, th*tile] wrapped-compact tiles -> full symmetric [na, na]: the
    whole-matrix unwrap `all_vs_all_mash_pallas` made before ISSUE 48, kept
    here as the oracle of `_assemble_symmetric`."""
    na = compact.shape[0]
    t = na // tile
    th = compact.shape[1] // tile
    out = np.empty((na, na), dtype=compact.dtype)
    for i in range(t):
        rows = slice(i * tile, (i + 1) * tile)
        for jj in range(th):
            j = (i + jj) % t
            cols = slice(j * tile, (j + 1) * tile)
            blk = compact[rows, jj * tile : (jj + 1) * tile]
            out[rows, cols] = blk
            out[cols, rows] = blk.T
    return out


@pytest.mark.parametrize("counts_kind", ["full", "some_short", "one_zero"])
@pytest.mark.parametrize("ragged_n", [False, True])
@pytest.mark.parametrize("t", [1, 2, 3, 4, 5])
def test_tilewise_assemble_is_bit_equal_to_the_whole_matrix_transform(rng, t, ragged_n, counts_kind):
    """ISSUE 48: the dense grid's counts become distances a tile at a time.
    Every float32 must be the one the whole-matrix unwrap + transform gave:
    odd and even t (an even t's last wrapped column holds its tile pairs
    twice), n on a tile edge and not, short and empty sketches."""
    from drep_tpu.ops.pallas_mash import _assemble_symmetric, shared_counts_to_distance

    tile, width, k = 8, 16, 21
    rows = t * tile
    n = rows - 3 if ragged_n else rows
    counts = np.full(n, width, np.int32)
    if counts_kind != "full":
        counts[::3] = rng.integers(1, width, size=len(counts[::3]))
    if counts_kind == "one_zero":
        counts[n // 2] = 0
    padded = np.zeros(rows, np.int32)
    padded[:n] = counts
    # a symmetric matrix of counts, as the kernel gives: shared <= min(|A|, |B|)
    full = np.triu(rng.integers(0, width + 1, size=(rows, rows)))
    full = np.minimum(full + np.triu(full, 1).T, np.minimum(padded[:, None], padded[None, :]))
    th = t // 2 + 1
    compact = np.empty((rows, th * tile), np.int32)
    for i in range(t):
        for jj in range(th):
            j = (i + jj) % t
            compact[i * tile : (i + 1) * tile, jj * tile : (jj + 1) * tile] = (
                full[i * tile : (i + 1) * tile, j * tile : (j + 1) * tile])
    shared = _unwrap_symmetric(compact, tile)
    np.testing.assert_array_equal(shared, full)  # the oracle reads the layout the kernel writes
    want_d, want_j = shared_counts_to_distance(shared[:n, :n], counts, counts, width, k)

    got_d, got_j, tiles, cells = _assemble_symmetric(compact, counts, width, k, tile, jaccard=True)
    assert got_d.dtype == got_j.dtype == np.float32
    np.testing.assert_array_equal(got_d.view(np.uint32), want_d.view(np.uint32))
    np.testing.assert_array_equal(got_j.view(np.uint32), want_j.view(np.uint32))
    # every unordered tile pair once, every cell once
    assert (tiles, cells) == (t * (t + 1) // 2, n * n)
    only_d, no_j, *_ = _assemble_symmetric(compact, counts, width, k, tile)
    assert no_j is None
    np.testing.assert_array_equal(only_d.view(np.uint32), want_d.view(np.uint32))


def test_pallas_mash_rectangular_blocks(rng):
    s = 64
    a = pack_sketches(_sketch_set(rng, 5, s), [f"a{i}" for i in range(5)], s)
    b = pack_sketches(_sketch_set(rng, 7, s), [f"b{i}" for i in range(7)], s)
    # one shared id space: re-pack together, then split
    both = pack_sketches(
        _sketch_set(rng, 12, s), [f"g{i}" for i in range(12)], s
    )
    a_ids, b_ids = both.ids[:5], both.ids[5:]
    a_cnt, b_cnt = both.counts[:5], both.counts[5:]
    want_d, _ = mash_distance_tile(a_ids, a_cnt, b_ids, b_cnt, k=21)
    got_d, _ = mash_distance_tile_pallas(a_ids, a_cnt, b_ids, b_cnt, k=21)
    assert got_d.shape == (5, 7)
    np.testing.assert_allclose(got_d, np.asarray(want_d), atol=1e-7)
    del a, b  # only the shared-vocab split is meaningful


@pytest.mark.parametrize("r_iter", [2, 4])
def test_rows_per_iter_batching_equals_default(rng, monkeypatch, r_iter):
    """The row-batched kernel variant (R a-rows merged per loop iteration,
    DREP_TPU_MASH_ROWS_PER_ITER) is a pure perf knob: results must be
    bit-identical to the default R=1 path on both grid layouts."""
    from drep_tpu.ops.minhash import all_vs_all_mash
    from drep_tpu.ops.pallas_mash import all_vs_all_mash_pallas

    n, s = 9, 64
    packed = pack_sketches(_sketch_set(rng, n, s), [f"g{i}" for i in range(n)], s)
    want_d, want_j = all_vs_all_mash(packed, k=21, tile=8)
    monkeypatch.setenv("DREP_TPU_MASH_ROWS_PER_ITER", str(r_iter))
    got_d, got_j = all_vs_all_mash_pallas(packed, k=21, jaccard=True)
    np.testing.assert_allclose(got_d, want_d, atol=1e-7)
    np.testing.assert_allclose(got_j, want_j, atol=1e-7)

    both = pack_sketches(_sketch_set(rng, 12, s), [f"g{i}" for i in range(12)], s)
    a_ids, b_ids = both.ids[:5], both.ids[5:]
    a_cnt, b_cnt = both.counts[:5], both.counts[5:]
    monkeypatch.setenv("DREP_TPU_MASH_ROWS_PER_ITER", "1")
    want_rd, _ = mash_distance_tile_pallas(a_ids, a_cnt, b_ids, b_cnt, k=21)
    monkeypatch.setenv("DREP_TPU_MASH_ROWS_PER_ITER", str(r_iter))
    got_rd, _ = mash_distance_tile_pallas(a_ids, a_cnt, b_ids, b_cnt, k=21)
    np.testing.assert_array_equal(got_rd, want_rd)


def test_merge_sorted_rows_equals_sort(rng):
    import jax.numpy as jnp

    from drep_tpu.ops.merge import merge_sorted_rows

    a = np.sort(rng.integers(0, 1 << 20, size=(7, 256)).astype(np.int32), axis=1)
    b = np.sort(rng.integers(0, 1 << 20, size=(7, 256)).astype(np.int32), axis=1)
    got = np.asarray(merge_sorted_rows(jnp.asarray(a), jnp.asarray(b)))
    want = np.sort(np.concatenate([a, b], axis=1), axis=1)
    np.testing.assert_array_equal(got, want)


def test_merge_rejects_non_pow2():
    import jax.numpy as jnp

    from drep_tpu.ops.merge import merge_sorted_rows

    with pytest.raises(ValueError):
        merge_sorted_rows(jnp.zeros((2, 100), jnp.int32), jnp.zeros((2, 100), jnp.int32))

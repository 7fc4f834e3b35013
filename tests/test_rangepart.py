"""Range-partitioned intersection paths vs numpy oracles.

The production regime (SURVEY.md §7 hard part (c)): 4 Mb genomes at the
default scale=200 give ~20k-wide scaled sketches — past the single-call
indicator budget (MATMUL_BUDGET_ELEMS). The MXU matmul extends by range
partitioning (ops/rangepart.py); these tests pin (a) the partition
machinery itself, (b) exact oracle equality of the vocab-chunked MXU
matmul at every vocabulary and that it is the one kernel a TPU takes
beyond the budget, and (c) that the jnp merge tiles obey the shared
HBM-temp cap.
"""

import numpy as np
import pytest

from drep_tpu.ops.merge import cap_merge_tile, next_pow2
from drep_tpu.ops.minhash import PAD_ID
from drep_tpu.ops.rangepart import MIN_BUCKET_WIDTH, partition_by_range


def _sorted_rows(rng, n, max_len, vocab):
    """Sorted unique PAD-padded rows over a given id vocabulary size.
    Row 0 is pinned to max_len so the matrix width is deterministic."""
    lens = rng.integers(0, max_len + 1, size=n)
    lens[0] = max_len
    rows = [
        np.unique(rng.choice(vocab, size=m, replace=False).astype(np.int32))
        for m in lens
    ]
    width = max(max((len(r) for r in rows), default=1), 1)
    ids = np.full((n, width), PAD_ID, dtype=np.int32)
    for i, r in enumerate(rows):
        ids[i, : len(r)] = r
    return ids


def _oracle_inter(a_ids, b_ids):
    out = np.zeros((a_ids.shape[0], b_ids.shape[0]), dtype=np.int32)
    for i in range(a_ids.shape[0]):
        ai = a_ids[i][a_ids[i] != PAD_ID]
        for j in range(b_ids.shape[0]):
            bj = b_ids[j][b_ids[j] != PAD_ID]
            out[i, j] = len(np.intersect1d(ai, bj))
    return out


def test_partition_reconstructs_rows(rng):
    ids = _sorted_rows(rng, 12, 700, 20_000)
    seen = [np.empty(0, np.int32)] * 12
    prev_origin = -1
    for origin, (bucket,) in partition_by_range([ids], MIN_BUCKET_WIDTH):
        assert origin > prev_origin  # buckets arrive in disjoint id order
        prev_origin = origin
        assert bucket.shape[1] >= MIN_BUCKET_WIDTH
        assert bucket.shape[1] == next_pow2(bucket.shape[1])  # pow2-bucketed
        real_per_row = (bucket != PAD_ID).sum(axis=1).max()
        assert real_per_row <= MIN_BUCKET_WIDTH
        for i in range(12):
            vals = bucket[i][bucket[i] != PAD_ID]
            assert (np.diff(vals) > 0).all()  # each bucket row stays sorted
            seen[i] = np.concatenate([seen[i], vals])
    for i in range(12):
        np.testing.assert_array_equal(seen[i], ids[i][ids[i] != PAD_ID])


def test_partition_shared_boundaries_across_matrices(rng):
    a = _sorted_rows(rng, 6, 500, 30_000)
    b = _sorted_rows(rng, 4, 500, 30_000)
    inter = np.zeros((6, 4), np.int32)
    for _origin, (ar, br) in partition_by_range([a, b], 256):
        inter += _oracle_inter(ar, br)
    np.testing.assert_array_equal(inter, _oracle_inter(a, b))


def test_partition_rejects_sub_lane_budget():
    with pytest.raises(ValueError):
        list(partition_by_range([np.zeros((1, 4), np.int32)], 64))


def test_stacked_vocab_chunks_rebase_and_reconstruct(rng):
    """Every chunk of the stacked tensor holds exactly its id range,
    rebased to origin; chunks together reconstruct the original rows."""
    from drep_tpu.ops.containment import _stacked_vocab_chunks

    from drep_tpu.ops.minhash import pad_sentinel

    ids = _sorted_rows(rng, 8, 400, 50_000)
    v_chunk = 8192
    stacked = _stacked_vocab_chunks(ids, v_chunk, m_pad=16)
    assert stacked.dtype == np.uint16  # chunk < 2^16 ships link-compressed
    pad = pad_sentinel(stacked.dtype)
    assert stacked.shape[1] == 16 and (stacked[:, 8:] == pad).all()
    seen = [np.empty(0, np.int64)] * 8
    for r in range(stacked.shape[0]):
        real = stacked[r][stacked[r] != pad]
        if real.size:
            assert real.min() >= 0 and real.max() < v_chunk
        for i in range(8):
            vals = stacked[r, i][stacked[r, i] != pad].astype(np.int64) + r * v_chunk
            seen[i] = np.concatenate([seen[i], vals])
    for i in range(8):
        np.testing.assert_array_equal(seen[i], ids[i][ids[i] != PAD_ID].astype(np.int64))


def test_merge_tile_cap_obeys_the_hbm_temp_budget():
    """The jnp sort-merge tiles (parallel/streaming.py) obey the shared
    HBM-temp budget: a fixed 128-tile at width 32768 would materialize
    ~4.3 GB per merge temporary."""
    from drep_tpu.ops.merge import SORT_TILE_BUDGET_ELEMS

    tile = cap_merge_tile(128, 32768)
    assert tile * tile * 2 * next_pow2(32768) <= SORT_TILE_BUDGET_ELEMS
    assert tile == 64
    assert 128 * 128 * 2 * next_pow2(32768) > SORT_TILE_BUDGET_ELEMS


# ---- beyond the one-shot budget: the chunked matmul, at every shape ----------


def _sparse_cluster(rng, n, hashes):
    """`n` sketches of about `hashes` hashes that share almost nothing, an
    empty row and a one-hash row among them: ids are dense ranks, so the
    vocabulary is about n * hashes — far wider than any row."""
    sketches = [
        np.unique(rng.integers(0, 1 << 60, size=hashes).astype(np.uint64)) for _ in range(n)
    ]
    sketches[1] = np.union1d(sketches[0][::2], sketches[1])[:hashes]  # one pair that shares
    sketches[2] = sketches[2][:0]
    sketches[3] = sketches[0][:1]  # its one hash is row 0's
    return sketches


def _merge_units(width: int) -> int:
    s2 = max(128, next_pow2(width))
    return 2 * s2 * ((2 * s2).bit_length() - 1)


# (width, rows, hashes a row, vocabulary over 47 x the width's merge units?):
# the wide-vocabulary shapes are the region a deleted cost constant gave to
# a VPU merge kernel; the last is a shape well under it
BEYOND_BUDGET_SHAPES = [(128, 1000, 120, True), (256, 1000, 250, True), (256, 200, 250, False)]


def _beyond_budget_pack(rng, width, n, hashes, wide, monkeypatch):
    import drep_tpu.ops.containment as cont

    packed = cont.pack_scaled_sketches(
        _sparse_cluster(rng, n, hashes), [f"g{i}" for i in range(n)]
    )
    assert packed.ids.shape[1] == width
    v_pad = cont.matmul_vocab_pad(packed)
    assert (v_pad > 47 * _merge_units(width)) == wide
    # several chunks, and no one-shot call
    monkeypatch.setattr(cont, "MATMUL_BUDGET_ELEMS", cont.matmul_rows_pad(n) * 8193)
    assert not cont.one_shot_fits(packed.n, v_pad)
    return packed


@pytest.mark.parametrize("width,n,hashes,wide", BEYOND_BUDGET_SHAPES)
def test_chunked_matmul_equals_the_tiles_at_any_vocabulary(rng, width, n, hashes, wide, monkeypatch):
    from drep_tpu.ops.containment import (
        all_vs_all_containment,
        all_vs_all_containment_matmul_chunked,
    )

    packed = _beyond_budget_pack(rng, width, n, hashes, wide, monkeypatch)
    ani, cov = all_vs_all_containment_matmul_chunked(packed, k=21)
    want_ani, want_cov = all_vs_all_containment(packed, k=21)
    np.testing.assert_array_equal(cov, want_cov)
    np.testing.assert_array_equal(ani, want_ani)
    assert cov[2].sum() == cov[:, 2].sum() == 1.0  # the empty row: only its pinned diagonal
    assert cov[3, 0] == 1.0 and 0 < cov[0, 3] < 0.01  # the one-hash row


@pytest.mark.parametrize("width,n,hashes,wide", BEYOND_BUDGET_SHAPES)
def test_beyond_the_budget_a_tpu_books_matmul_chunked(rng, width, n, hashes, wide, monkeypatch):
    """`containment_matrices` with the platform patched to a TPU: one kernel
    beyond the budget, whatever the vocabulary; off a TPU the tiles."""
    import types

    import jax

    from drep_tpu.cluster.engines import containment_matrices
    from drep_tpu.utils.profiling import counters

    packed = _beyond_budget_pack(rng, width, n, hashes, wide, monkeypatch)
    want = containment_matrices(packed, 21, mesh_shape=1)
    counters.reset()
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [types.SimpleNamespace(platform="tpu")])
    got = containment_matrices(packed, 21, mesh_shape=1)
    assert counters.paths == {"matmul_chunked": 1}
    assert counters.report(device=False)["secondary_chunked_calls"][0]["chunks"] >= 2
    counters.reset()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_chunked_matmul_matches_one_shot(rng):
    """The vocab-chunked MXU path must exactly equal the single-indicator
    matmul (and therefore the searchsorted path it is tested against)."""
    from drep_tpu.ops.containment import (
        all_vs_all_containment_matmul,
        all_vs_all_containment_matmul_chunked,
        matmul_vocab_pad,
        pack_scaled_sketches,
    )

    # vocab must span several 8192-wide chunks for the chunking to engage
    sketches = [
        np.unique(
            rng.integers(0, 1 << 40, size=int(rng.integers(50, 800))).astype(np.uint64)
        )
        for _ in range(33)
    ]
    packed = pack_scaled_sketches(sketches, [f"g{i}" for i in range(33)])
    v_pad = matmul_vocab_pad(packed)
    assert v_pad > 8192  # multi-chunk for the chunked path below

    import drep_tpu.ops.containment as cont

    orig = cont.MATMUL_BUDGET_ELEMS
    cont.MATMUL_BUDGET_ELEMS = 1 << 15  # force v_chunk to the 8192 floor
    try:
        ani_c, cov_c = all_vs_all_containment_matmul_chunked(packed, k=21)
    finally:
        cont.MATMUL_BUDGET_ELEMS = orig
    ani_1, cov_1 = all_vs_all_containment_matmul(packed, k=21)
    np.testing.assert_array_equal(cov_c, cov_1)
    np.testing.assert_array_equal(ani_c, ani_1)


def test_rect_matmul_matches_oracle(rng):
    """Rectangular chunked intersection counts (the greedy path's TPU
    route) vs the numpy oracle, across the chunking boundary."""
    from drep_tpu.ops.containment import intersect_counts_matmul_rect

    a = _sorted_rows(rng, 7, 500, 40_000)
    b = _sorted_rows(rng, 12, 500, 40_000)
    import drep_tpu.ops.containment as cont

    got = intersect_counts_matmul_rect(a, b)
    np.testing.assert_array_equal(got, _oracle_inter(a, b))

    orig = cont.MATMUL_BUDGET_ELEMS
    cont.MATMUL_BUDGET_ELEMS = 1 << 15  # force multi-chunk
    try:
        got_chunked = intersect_counts_matmul_rect(a, b)
    finally:
        cont.MATMUL_BUDGET_ELEMS = orig
    np.testing.assert_array_equal(got_chunked, _oracle_inter(a, b))


def test_greedy_matmul_path_equals_gather_path(rng, monkeypatch):
    """Greedy clustering must produce identical Ndb/labels through the
    rectangular-matmul route (TPU) and the gather tiles (CPU default)."""
    import jax

    import drep_tpu.cluster.greedy as greedy_mod
    from drep_tpu.cluster.greedy import greedy_secondary_cluster
    from drep_tpu.ingest import DEFAULT_SCALE, GenomeSketches

    import pandas as pd

    n = 40
    sketches = []
    pool = np.unique(rng.integers(0, 1 << 40, size=4000, dtype=np.uint64))
    for i in range(n):
        keep = pool[rng.random(len(pool)) < (0.9 if i % 2 else 0.5)]
        own = np.unique(rng.integers(0, 1 << 40, size=200, dtype=np.uint64))
        sketches.append(np.unique(np.concatenate([keep, own])))
    gdb = pd.DataFrame(
        {
            "genome": [f"g{i}" for i in range(n)],
            "length": 1_000_000,
            "N50": 10_000,
            "contigs": 10,
            "n_kmers": [len(s) * 50 for s in sketches],
        }
    )
    gs = GenomeSketches(
        names=list(gdb["genome"]), gdb=gdb, bottom=[], scaled=sketches,
        k=21, sketch_size=1000, scale=DEFAULT_SCALE,
    )
    bdb = pd.DataFrame({"genome": gs.names, "location": gs.names})
    kw = {"S_ani": 0.95, "cov_thresh": 0.1}

    ndb_g, labels_g = greedy_secondary_cluster(gs, bdb, list(range(n)), 1, kw, block=16)

    real_platform = jax.devices()[0].platform
    if real_platform == "tpu":  # first run already took the matmul path
        pytest.skip("gather-vs-matmul comparison needs a non-tpu default")

    class FakeDev:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeDev()] if not a else [FakeDev()])
    try:
        ndb_m, labels_m = greedy_secondary_cluster(gs, bdb, list(range(n)), 1, kw, block=16)
    finally:
        monkeypatch.undo()
    np.testing.assert_array_equal(labels_g, labels_m)
    pd.testing.assert_frame_equal(
        ndb_g.frame().reset_index(drop=True), ndb_m.frame().reset_index(drop=True)
    )

"""Streaming out-of-core primary: edges, linkage, checkpoint/resume.

The streaming path must produce the same primary partition as the dense
path for BOTH linkage families: sparse UPGMA over the retained edge graph
== scipy average linkage (when no merge touches an unobserved pair, which
it certifies), and connected components at a distance cutoff ==
single-linkage fcluster at that cutoff.
"""

import glob
import os

import numpy as np
import pytest

from drep_tpu.ops.minhash import PAD_ID, PackedSketches, all_vs_all_mash
from drep_tpu.ops.linkage import cluster_hierarchical
from drep_tpu.parallel.streaming import (
    connected_components,
    streaming_mash_edges,
    streaming_primary_clusters,
)


def _random_packed(n=60, s=64, n_groups=5, seed=0):
    """Sketches built from group-specific hash pools so that genomes in the
    same group overlap heavily (small Mash distance) and cross-group pairs
    do not."""
    rng = np.random.default_rng(seed)
    ids = np.full((n, s), PAD_ID, dtype=np.int32)
    counts = np.zeros(n, dtype=np.int32)
    pools = [
        np.sort(rng.choice(2**20, size=s * 2, replace=False).astype(np.int32))
        for _ in range(n_groups)
    ]
    for i in range(n):
        pool = pools[i % n_groups]
        pick = np.sort(rng.choice(pool, size=s, replace=False))
        ids[i] = pick
        counts[i] = s
    return PackedSketches(ids=ids, counts=counts, names=[f"g{i}" for i in range(n)])


def _canon(labels):
    """Canonical partition form: map labels to first-occurrence order."""
    seen = {}
    out = []
    for lab in labels:
        if lab not in seen:
            seen[lab] = len(seen) + 1
        out.append(seen[lab])
    return out


def test_connected_components_basic():
    ii = np.array([0, 1, 3])
    jj = np.array([1, 2, 4])
    labels = connected_components(6, ii, jj)
    assert _canon(labels) == [1, 1, 1, 2, 2, 3]


def test_connected_components_no_edges():
    labels = connected_components(4, np.empty(0, np.int64), np.empty(0, np.int64))
    assert list(labels) == [1, 2, 3, 4]


def test_streaming_edges_match_dense():
    packed = _random_packed()
    cutoff = 0.1
    dist, _ = all_vs_all_mash(packed, k=21)
    ii, jj, dd, pairs = streaming_mash_edges(packed, k=21, cutoff=cutoff, block=16)
    assert pairs == packed.n * (packed.n - 1) // 2  # everything computed fresh
    dense_keep = {
        (i, j)
        for i in range(packed.n)
        for j in range(i + 1, packed.n)
        if dist[i, j] <= cutoff
    }
    assert set(zip(ii.tolist(), jj.tolist())) == dense_keep
    np.testing.assert_allclose(dd, dist[ii, jj], rtol=1e-6)


def test_streaming_edge_budget_overflow_falls_back_dense(monkeypatch):
    """A tile with more survivors than the per-tile device->host edge
    budget must fall back to the dense readback with identical results —
    correctness never depends on EDGE_BUDGET."""
    import drep_tpu.parallel.streaming as streaming_mod

    packed = _random_packed()
    cutoff = 2.0  # keep EVERY pair: every tile overflows a tiny budget
    want = streaming_mash_edges(packed, k=21, cutoff=cutoff, block=16)
    monkeypatch.setattr(streaming_mod, "EDGE_BUDGET", 4)
    got = streaming_mash_edges(packed, k=21, cutoff=cutoff, block=16)
    for a, b in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(a, b)
    assert got[3] == want[3]


def test_streaming_partition_matches_single_linkage():
    packed = _random_packed()
    p_ani = 0.9
    labels_s, _, _ = streaming_primary_clusters(
        packed, k=21, p_ani=p_ani, block=16, cluster_alg="single"
    )
    dist, _ = all_vs_all_mash(packed, k=21)
    labels_d, _ = cluster_hierarchical(dist, 1.0 - p_ani, method="single")
    assert _canon(labels_s) == _canon(labels_d)


def test_streaming_partition_matches_average_linkage():
    """Default --clusterAlg average must survive the streaming switch: the
    sparse UPGMA partition equals scipy's dense average linkage (the edge
    band up to warn_dist is what makes the averages computable)."""
    packed = _random_packed()
    p_ani = 0.9
    labels_s, _, _ = streaming_primary_clusters(
        packed, k=21, p_ani=p_ani, block=16, keep_dist=0.25, cluster_alg="average"
    )
    dist, _ = all_vs_all_mash(packed, k=21)
    labels_d, _ = cluster_hierarchical(dist, 1.0 - p_ani, method="average")
    assert _canon(labels_s) == _canon(labels_d)


def test_streaming_checkpoint_resume(tmp_path):
    packed = _random_packed(n=40, s=32)
    ckpt = str(tmp_path / "ckpt")
    ii1, jj1, dd1, p1 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    shards = sorted(glob.glob(os.path.join(ckpt, "row_*.npz")))
    assert len(shards) == 5  # 40 / 8
    assert p1 == 40 * 39 // 2

    # delete two shards: resume must recompute exactly those and agree;
    # pairs_computed counts only the recomputed stripes
    os.remove(shards[1])
    os.remove(shards[3])
    ii2, jj2, dd2, p2 = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    assert set(zip(ii2.tolist(), jj2.tolist())) == set(zip(ii1.tolist(), jj1.tolist()))
    assert 0 < p2 < p1

    # a corrupt shard is detected and recomputed, not fatal
    with open(shards[2], "wb") as f:
        f.write(b"not an npz")
    ii2b, jj2b, _, _ = streaming_mash_edges(packed, k=21, cutoff=0.2, block=8, checkpoint_dir=ckpt)
    assert set(zip(ii2b.tolist(), jj2b.tolist())) == set(zip(ii1.tolist(), jj1.tolist()))

    # changed arguments invalidate the checkpoint (meta mismatch -> rebuild)
    streaming_mash_edges(packed, k=21, cutoff=0.3, block=8, checkpoint_dir=ckpt)
    import json

    with open(os.path.join(ckpt, "meta.json")) as f:
        assert json.load(f)["cutoff"] == 0.3

    # different genome content at identical shapes also invalidates (the
    # int32 ids are a run-specific vocab remap — stale shards are garbage)
    other = _random_packed(n=40, s=32, seed=9)
    _, _, _, p_other = streaming_mash_edges(other, k=21, cutoff=0.3, block=8, checkpoint_dir=ckpt)
    assert p_other == 40 * 39 // 2  # nothing was resumed


def test_streaming_via_controller(tmp_path, genome_paths):
    """End-to-end: --streaming_primary through the cluster controller."""
    from drep_tpu.workflows import compare_wrapper

    cdb = compare_wrapper(
        str(tmp_path / "wd"),
        genome_paths,
        streaming_primary=True,
        skip_plots=True,
    )
    assert len(cdb) == len(genome_paths)
    # Mdb was stored sparse (diagonal present)
    import pandas as pd

    mdb = pd.read_csv(tmp_path / "wd" / "data_tables" / "Mdb.csv")
    assert (mdb["genome1"] == mdb["genome2"]).sum() == len(genome_paths)


def test_threshold_crossing_keeps_average_linkage(tmp_path, genome_paths):
    """Both sides of --streaming_threshold with default flags (clusterAlg
    average): the partition must be IDENTICAL whether the run streams or
    takes the dense path — no linkage-family discontinuity at the
    boundary (VERDICT r2 item 5)."""
    from drep_tpu.workflows import compare_wrapper

    dense = compare_wrapper(
        str(tmp_path / "wd_dense"), genome_paths,
        streaming_threshold=10_000, skip_plots=True,
    )
    streamed = compare_wrapper(
        str(tmp_path / "wd_stream"), genome_paths,
        streaming_threshold=2, skip_plots=True,  # force auto-streaming
    )
    d = dense.set_index("genome")
    s = streamed.set_index("genome")
    for g in d.index:
        assert d.loc[g, "primary_cluster"] == s.loc[g, "primary_cluster"], g
        assert d.loc[g, "secondary_cluster"] == s.loc[g, "secondary_cluster"], g


def test_streaming_unsupported_alg_errors_via_controller(tmp_path, genome_paths):
    from drep_tpu.workflows import compare_wrapper

    with pytest.raises(ValueError, match="average or single"):
        compare_wrapper(
            str(tmp_path / "wd"), genome_paths,
            streaming_primary=True, clusterAlg="complete", skip_plots=True,
        )


def test_overlap_ingest_identical_results(tmp_path, genome_paths):
    """The compile-warmup overlap must not change results: identical Cdb
    with --no_overlap_ingest (it computes throwaway data by construction;
    this pins it). The overlapped run uses a SPAWNED ingest pool — the
    combination the overlap guard used to forbid when ingest forked."""
    from drep_tpu.workflows import compare_wrapper

    on = compare_wrapper(
        str(tmp_path / "wd_on"), genome_paths,
        streaming_primary=True, overlap_ingest=True, skip_plots=True,
        processes=2,
    )
    off = compare_wrapper(
        str(tmp_path / "wd_off"), genome_paths,
        streaming_primary=True, overlap_ingest=False, skip_plots=True,
        processes=2,  # overlap must stay the ONLY variable between runs
    )
    on = on.sort_values("genome").reset_index(drop=True)
    off = off.sort_values("genome").reset_index(drop=True)
    assert on[["genome", "primary_cluster", "secondary_cluster"]].equals(
        off[["genome", "primary_cluster", "secondary_cluster"]]
    )


def test_overlap_warmup_skipped_when_sketch_cache_hits(tmp_path, genome_paths, monkeypatch):
    """The warmup thread exists to hide the cold compile behind INGEST;
    when the workdir's sketch cache will hit (resumed runs, pre-planted
    workdirs) there is no ingest to hide behind — the controller must not
    start it."""
    import drep_tpu.parallel.streaming as streaming_mod
    from drep_tpu.workflows import compare_wrapper

    calls = []
    real = streaming_mod.warmup_streaming_compile
    monkeypatch.setattr(
        streaming_mod, "warmup_streaming_compile",
        lambda *a, **k: (calls.append(1), real(*a, **k)),
    )
    wd = str(tmp_path / "wd")
    compare_wrapper(wd, genome_paths, streaming_primary=True,
                    overlap_ingest=True, skip_plots=True)
    assert calls, "fresh run (no cache) must start the warmup"
    calls.clear()
    # invalidate the Cdb resume but keep the sketch cache: the second run
    # recomputes clustering from cached sketches — warmup must not start
    os.remove(os.path.join(wd, "data_tables", "Cdb.csv"))
    compare_wrapper(wd, genome_paths, streaming_primary=True,
                    overlap_ingest=True, skip_plots=True)
    assert not calls, "cache-hit run must skip the warmup thread"


def test_streaming_average_widens_zero_retention():
    """keep_dist <= cutoff would leave UPGMA no information beyond the
    cutoff (bound degenerates to connected components); the path must
    widen retention instead — identical partition to an explicit band."""
    packed = _random_packed()
    l0, _, _ = streaming_primary_clusters(
        packed, k=21, p_ani=0.9, block=16, keep_dist=0.0, cluster_alg="average"
    )
    l1, _, _ = streaming_primary_clusters(
        packed, k=21, p_ani=0.9, block=16, keep_dist=0.25, cluster_alg="average"
    )
    assert _canon(l0) == _canon(l1)


def test_streaming_plus_greedy_north_star_combo(tmp_path, genome_paths):
    """The 100k north-star configuration — streaming primary + greedy
    secondary — must compose and recover the fixture clustering."""
    from drep_tpu.workflows import compare_wrapper

    cdb = compare_wrapper(
        str(tmp_path / "wd"), genome_paths,
        streaming_primary=True, greedy_secondary_clustering=True,
        skip_plots=True,
    )
    c = cdb.set_index("genome")["secondary_cluster"]
    assert c["genome_A.fasta"] == c["genome_B.fasta"]
    assert c["genome_A.fasta"] != c["genome_C.fasta"]
    assert c["genome_D.fasta"] == c["genome_E.fasta"]
    assert cdb["secondary_cluster"].nunique() == 3

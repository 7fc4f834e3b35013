"""Small-cluster batching: one device call must equal per-cluster calls."""

import numpy as np
import pandas as pd
import pytest

from drep_tpu.cluster import dispatch
from drep_tpu.cluster.engines import secondary_jax_ani, secondary_jax_ani_batched
from drep_tpu.ingest import GenomeSketches


@pytest.fixture(scope="module")
def gs_many_small():
    rng = np.random.default_rng(3)
    n_clusters, per, s = 12, 4, 600
    names, scaled = [], []
    for c in range(n_clusters):
        pool = np.sort(
            rng.choice(np.uint64(1) << np.uint64(40), size=2 * s, replace=False).astype(np.uint64)
        )
        for m in range(per):
            names.append(f"c{c}m{m}")
            scaled.append(np.sort(rng.choice(pool, size=s, replace=False)))
    gdb = pd.DataFrame({"genome": names, "n_kmers": [len(x) for x in scaled]})
    return GenomeSketches(
        names=names, gdb=gdb, bottom=[x[:64] for x in scaled], scaled=scaled,
        k=21, sketch_size=64, scale=200,
    )


def test_batched_equals_per_cluster(gs_many_small):
    gs = gs_many_small
    clusters = [list(range(c * 4, c * 4 + 4)) for c in range(12)]
    batched = secondary_jax_ani_batched(gs, clusters)
    assert len(batched) == len(clusters)
    for cl, (ani_b, cov_b) in zip(clusters, batched):
        ani_s, cov_s = secondary_jax_ani(gs, cl)
        np.testing.assert_allclose(ani_b, ani_s, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(cov_b, cov_s, rtol=1e-5, atol=1e-6)


def test_batched_uses_clusterlocal_one_shot(gs_many_small):
    """Single-chip batched calls must ride the cluster-local pack (max
    single-cluster vocab, one-shot indicator) instead of beyond-budget
    chunked mega-calls over the union vocabulary — and the run record
    says so."""
    from drep_tpu.utils.profiling import counters

    gs = gs_many_small
    clusters = [list(range(c * 4, c * 4 + 4)) for c in range(12)]
    before = counters.paths.get("one_shot_clusterlocal", 0)
    secondary_jax_ani_batched(gs, clusters)
    assert counters.paths.get("one_shot_clusterlocal", 0) - before == 1
    assert counters.report()["secondary_paths"]["one_shot_clusterlocal"] >= 1


def test_batched_falls_back_when_local_vocab_beyond_budget(gs_many_small, monkeypatch):
    """A batch whose max single-cluster vocabulary exceeds the one-shot
    budget must fall back to the shared-vocabulary dispatch and still
    match per-cluster results."""
    monkeypatch.setattr("drep_tpu.ops.containment.MATMUL_BUDGET_ELEMS", 1 << 12)
    gs = gs_many_small
    clusters = [list(range(c * 4, c * 4 + 4)) for c in range(3)]
    batched = secondary_jax_ani_batched(gs, clusters)
    for cl, (ani_b, cov_b) in zip(clusters, batched):
        ani_s, cov_s = secondary_jax_ani(gs, cl)
        np.testing.assert_allclose(ani_b, ani_s, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(cov_b, cov_s, rtol=1e-5, atol=1e-6)


def test_clusterlocal_pack_ranks_and_extent():
    """Per-cluster ranks are local (clusters reuse id values) and v_extent
    is the max cluster vocabulary, not the union."""
    from drep_tpu.ops.containment import pack_scaled_sketches_clusterlocal
    from drep_tpu.ops.minhash import PAD_ID

    g0 = [np.array([10, 20, 30], np.uint64), np.array([20, 30], np.uint64)]
    g1 = [np.array([1000, 2000], np.uint64), np.array([2000, 3000, 4000, 5000], np.uint64)]
    packed, v_extent = pack_scaled_sketches_clusterlocal([g0, g1], list("abcd"))
    assert v_extent == 5  # cluster 1's vocab {1000,2000,3000,4000,5000}
    assert packed.ids.shape[1] == 128  # lane-width pad floor
    # tiny vocab -> the link-compressed uint16 layout (0xFFFF pad)
    assert packed.ids.dtype == np.uint16
    pad = np.uint16(0xFFFF) if packed.ids.dtype == np.uint16 else PAD_ID
    row = lambda i: packed.ids[i][packed.ids[i] != pad].tolist()
    assert row(0) == [0, 1, 2] and row(1) == [1, 2]  # cluster-0 local ranks
    assert row(2) == [0, 1] and row(3) == [1, 2, 3, 4]  # cluster-1 reuses 0..
    assert packed.counts.tolist() == [3, 2, 2, 4]


def test_batched_registered():
    assert dispatch.get_secondary_batched("jax_ani") is not None
    assert dispatch.get_secondary_batched("fastANI") is None  # subprocess: per-cluster

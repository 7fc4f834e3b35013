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


def _ragged_clusters(rng, sizes, lo=5, hi=400, space=1 << 40):
    """Clusters of sorted unique uint64 sketches drawn from a pool per
    cluster (members overlap, as primary clustering guarantees)."""
    groups = []
    for m in sizes:
        pool = rng.choice(np.uint64(space), size=2 * hi, replace=False).astype(np.uint64)
        groups.append(
            [np.sort(rng.choice(pool, size=int(rng.integers(lo, hi)), replace=False)) for _ in range(m)]
        )
    return groups


def _pack_cases():
    rng = np.random.default_rng(25)
    empty = np.zeros(0, np.uint64)
    wide = [np.arange(0, 40000, dtype=np.uint64) * 7, np.arange(30000, 70000, dtype=np.uint64) * 7]
    return {
        # name: (clusters, processes)
        "ragged_p1": (_ragged_clusters(rng, [3, 1, 5, 2, 4]), 1),
        "ragged_p2_more_clusters_than_workers": (_ragged_clusters(rng, [2, 6, 3, 3, 1, 4, 2]), 2),
        "ragged_p8_fewer_clusters_than_workers": (_ragged_clusters(rng, [4, 2, 7]), 8),
        "ragged_p8_more_clusters_than_workers": (_ragged_clusters(rng, [2] * 9 + [5] * 4, hi=150), 8),
        "single_member_cluster_alone": (_ragged_clusters(rng, [1]), 4),
        "member_with_empty_sketch": (
            [[np.array([3, 9, 27], np.uint64), empty], [empty, np.array([5], np.uint64), empty]], 2,
        ),
        "every_sketch_empty": ([[empty, empty], [empty]], 2),
        # 70,000 distinct hashes in one cluster: past uint16, the int32 / PAD_ID layout
        "vocabulary_reaches_0xFFFF": ([wide, _ragged_clusters(rng, [3])[0]], 2),
        # exactly 0xFFFF distinct: the first size the uint16 sentinel cannot spare
        "vocabulary_exactly_0xFFFF": ([[np.arange(0xFFFF, dtype=np.uint64)], [np.array([1, 2], np.uint64)]], 2),
        "vocabulary_0xFFFE_still_uint16": ([[np.arange(0xFFFE, dtype=np.uint64)], [np.array([1, 2], np.uint64)]], 2),
    }


@pytest.mark.parametrize("case", sorted(_pack_cases()))
def test_clusterlocal_pack_equals_double_loop_oracle(case):
    """ids (values, dtype, pad value, width), counts and v_extent against
    an oracle written here: per cluster np.unique of its hashes, per row
    np.searchsorted, a plain double loop (ISSUE 25: the pack ranks
    clusters on a thread pool and writes rows by slice; the matrix must
    not change by a bit for any pool width)."""
    from drep_tpu.ops.containment import pack_scaled_sketches_clusterlocal
    from drep_tpu.ops.minhash import PAD_ID

    groups, processes = _pack_cases()[case]
    names = [f"g{c}_{m}" for c, g in enumerate(groups) for m in range(len(g))]
    packed, v_extent = pack_scaled_sketches_clusterlocal(groups, names, processes=processes)

    vocabs = [np.unique(np.concatenate(g)) for g in groups]
    want_extent = max(1, max(len(v) for v in vocabs))
    want_dtype, want_pad = (np.uint16, 0xFFFF) if want_extent < 0xFFFF else (np.int32, PAD_ID)
    longest = max(len(s) for g in groups for s in g)
    want_width = max(128, 1 << (max(longest, 1) - 1).bit_length())
    want = np.full((len(names), want_width), want_pad, dtype=want_dtype)
    r = 0
    for g, vocab in zip(groups, vocabs):
        for s in g:
            for j, h in enumerate(s):
                want[r, j] = np.searchsorted(vocab, h)
            r += 1

    assert v_extent == want_extent
    assert packed.ids.dtype == want_dtype and packed.ids.shape == want.shape
    assert packed.ids.tobytes() == want.tobytes()
    assert packed.counts.dtype == np.int32
    assert packed.counts.tolist() == [len(s) for g in groups for s in g]
    assert packed.names == names
    # the width of the pool is no input of the result
    for p in (1, 3):
        again, v_again = pack_scaled_sketches_clusterlocal(groups, names, processes=p)
        assert v_again == v_extent and again.ids.dtype == packed.ids.dtype
        assert again.ids.tobytes() == packed.ids.tobytes()


@pytest.mark.parametrize(
    "processes, n_groups, cores, want",
    [(1, 100, 8, 1), (6, 127, 13, 6), (6, 3, 13, 3), (8, 127, 2, 2), (0, 5, 8, 1), (4, 1, 8, 1)],
)
def test_clusterlocal_pack_workers(monkeypatch, processes, n_groups, cores, want):
    """Pool width = min(-p, clusters in the batch, cores this process may
    use), never under 1; 1 runs inline."""
    from drep_tpu.ops import containment

    monkeypatch.setattr(containment, "_usable_cores", lambda: cores)
    assert containment.clusterlocal_pack_workers(processes, n_groups) == want
    if want == 1:
        # no pool is made: ranking a cluster off the main thread would show here
        def no_pool(*a, **k):
            raise AssertionError("a pool was made for one worker")

        monkeypatch.setattr(containment, "ThreadPoolExecutor", no_pool)
        g = [[np.array([1, 2], np.uint64)]] * n_groups
        containment.pack_scaled_sketches_clusterlocal(g, ["x"] * n_groups, processes=processes)


def test_pack_span_says_workers_and_hashes(gs_many_small, monkeypatch):
    """The `secondary/pack` span round the cluster-local pack carries the
    pool width used and the hashes ranked (profiler host plane + event
    log), and worker threads book no span of their own."""
    from drep_tpu.ops import containment
    from drep_tpu.utils import telemetry
    from drep_tpu.utils.profiling import counters

    seen = []
    real = telemetry.Span

    def spy(name, args):
        seen.append((name, dict(args)))
        return real(name, args)

    monkeypatch.setattr(telemetry, "Span", spy)
    monkeypatch.setattr(containment, "_usable_cores", lambda: 8)
    gs = gs_many_small
    clusters = [list(range(c * 4, c * 4 + 4)) for c in range(12)]
    off_main = {k for k in counters.phases if not k[1]}
    secondary_jax_ani_batched(gs, clusters, processes=5)
    packs = [a for n, a in seen if n == "secondary/pack" and "workers" in a]
    assert packs == [{"workers": 5, "hashes": 12 * 4 * 600}]
    assert {k for k in counters.phases if not k[1]} == off_main


def test_compare_hands_processes_to_batched_engine_and_p_changes_nothing(tmp_path, monkeypatch):
    """`_secondary_stage` hands dRep's `-p` to the batched engine (the
    pack's pool width comes from it, ISSUE 25), and a toy `compare` on
    planted sketches writes byte-equal Cdb.csv and Ndb.csv under `-p 1`
    and `-p 4`."""
    import os
    import shutil

    from drep_tpu import controller
    from drep_tpu.ingest import DEFAULT_SCALE, _save, sketch_args_snapshot
    from drep_tpu.ops import containment
    from drep_tpu.utils.synth import plant_genome_sketches
    from drep_tpu.workdir import WorkDirectory

    # the planted-workdir recipe of chip_smoke.py / benchmark/generators:
    # Bdb + sketch cache, so `compare <wd>` starts at the cluster stage
    gs, labels = plant_genome_sketches(120, np.random.default_rng(25), s_scaled=600)
    pristine = str(tmp_path / "pristine")
    wd = WorkDirectory(pristine)
    wd.store_db(
        pd.DataFrame({"genome": gs.names, "location": [f"/nonexistent/{g}" for g in gs.names]}),
        "Bdb",
    )
    _save(wd, gs)
    wd.store_arguments(
        "sketch", sketch_args_snapshot(gs.names, gs.k, gs.sketch_size, DEFAULT_SCALE, "splitmix64")
    )

    seen = []
    real_batched = dispatch.get_secondary_batched("jax_ani")

    def recording_batched(*a, **k):
        seen.append((k.get("processes"), containment.clusterlocal_pack_workers(k["processes"], len(a[1]))))
        return real_batched(*a, **k)

    monkeypatch.setitem(dispatch.SECONDARY_BATCHED, "jax_ani", recording_batched)
    monkeypatch.setattr(containment, "_usable_cores", lambda: 8)

    tables = {}
    for p in (1, 4):
        run = str(tmp_path / f"wd_p{p}")
        shutil.copytree(pristine, run)
        seen.clear()
        controller.main(["compare", run, "--skip_plots", "-p", str(p)])
        multi = sum(1 for c in np.bincount(labels) if c > 1)
        assert seen and {s[0] for s in seen} == {p}, seen
        assert seen[0][1] == min(p, multi, 8)  # the pool engaged under -p 4 only
        tables[p] = {
            t: open(os.path.join(run, "data_tables", t), "rb").read() for t in ("Cdb.csv", "Ndb.csv")
        }
        assert len(tables[p]["Ndb.csv"]) > 1000
    assert tables[1] == tables[4]
    cdb = pd.read_csv(os.path.join(run, "data_tables", "Cdb.csv"))
    assert cdb["secondary_cluster"].nunique() == len(set(labels.tolist()))


def test_batched_registered():
    assert dispatch.get_secondary_batched("jax_ani") is not None
    assert dispatch.get_secondary_batched("fastANI") is None  # subprocess: per-cluster

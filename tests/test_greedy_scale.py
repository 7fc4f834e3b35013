"""Greedy secondary clustering at moderate scale (vectorized-loop guard).

Builds synthetic GenomeSketches directly (no FASTA round-trip): 400 genomes
in 20 planted clusters. The greedy partition must match the planted truth,
and the run must stay fast — a regression to Python pair-loops would blow
the time budget immediately (400 genomes x ~20 reps was ~8k Python
iterations per block before vectorization).
"""

import time

import numpy as np
import pandas as pd
import pytest

from drep_tpu.cluster.greedy import greedy_secondary_cluster
from drep_tpu.ingest import GenomeSketches


@pytest.fixture(scope="module")
def synthetic():
    rng = np.random.default_rng(42)
    n_clusters, per_cluster, s = 20, 20, 800
    names, scaled, truth = [], [], []
    for c in range(n_clusters):
        pool = np.sort(
            rng.choice(np.uint64(1) << np.uint64(40), size=2 * s, replace=False).astype(np.uint64)
        )
        for m in range(per_cluster):
            # members share ~97% of their hashes with the pool
            pick = np.sort(rng.choice(pool, size=s, replace=False))
            names.append(f"c{c}m{m}")
            scaled.append(pick)
            truth.append(c)
    gdb = pd.DataFrame({"genome": names, "n_kmers": [len(s_) for s_ in scaled]})
    gs = GenomeSketches(
        names=names, gdb=gdb, bottom=[s_[:100] for s_ in scaled], scaled=scaled,
        k=21, sketch_size=100, scale=200,
    )
    return gs, truth


def test_greedy_recovers_planted_clusters(synthetic):
    gs, truth = synthetic
    m = len(gs.names)
    kw = {"S_ani": 0.95, "cov_thresh": 0.1}
    t0 = time.perf_counter()
    ndb, labels = greedy_secondary_cluster(gs, None, list(range(m)), pc=1, kw=kw)
    dt = time.perf_counter() - t0

    # partition must equal the planted clusters (labels up to renaming)
    by_label: dict[int, set] = {}
    for i, lab in enumerate(labels):
        by_label.setdefault(int(lab), set()).add(truth[i])
    assert all(len(v) == 1 for v in by_label.values()), "cluster mixing"
    assert len(by_label) == 20

    # comparisons recorded: every genome vs every rep existing when visited
    assert len(ndb) > 0
    assert set(ndb.frame().columns) >= {"reference", "querry", "ani", "alignment_coverage", "primary_cluster"}

    # generous ceiling: the vectorized path runs in a few seconds on CPU;
    # a Python pair-loop regression would take minutes
    assert dt < 60, f"greedy took {dt:.1f}s — pair-loop regression?"


def test_greedy_mesh_sharded_equals_single_device(synthetic, monkeypatch):
    """The mesh-sharded matmul route (candidate blocks sharded over the
    CPU test mesh, reps replicated — BASELINE config 5's 100k multi-chip
    greedy) must reproduce the single-device run exactly: same labels,
    same Ndb comparison set and values. DREP_TPU_GREEDY_MATMUL forces the
    matmul family off-TPU; mesh_shape picks the 8-device test mesh."""
    gs, _truth = synthetic
    m = len(gs.names)
    kw = {"S_ani": 0.95, "cov_thresh": 0.1}
    want_ndb, want_labels = greedy_secondary_cluster(gs, None, list(range(m)), pc=1, kw=kw)

    monkeypatch.setenv("DREP_TPU_GREEDY_MATMUL", "1")
    kw_mesh = {**kw, "mesh_shape": 8}
    got_ndb, got_labels = greedy_secondary_cluster(gs, None, list(range(m)), pc=1, kw=kw_mesh)

    np.testing.assert_array_equal(got_labels, want_labels)
    assert len(got_ndb) == len(want_ndb)
    for col in ("reference", "querry"):
        assert list(got_ndb.column(col)) == list(want_ndb.column(col))
    for col in ("ani", "alignment_coverage", "ref_coverage", "querry_coverage"):
        np.testing.assert_allclose(got_ndb.column(col), want_ndb.column(col), atol=1e-6, err_msg=col)

    # attribution recorded: the span of the device work, the route, the cluster's entry
    from drep_tpu.utils.profiling import counters

    rec = counters.report(device=False)
    assert rec["phases"]["secondary/greedy_wait"]["seconds"] > 0
    assert rec["secondary_paths"]["greedy_matmul"] >= 1
    assert rec["secondary_greedy_calls"][-1]["block_rows"] == 128 * 8  # the mesh's block
    assert rec["secondary_greedy_calls"][-1]["device_calls"] > 0


def test_greedy_matmul_single_device_equals_gather(synthetic, monkeypatch):
    """The NON-mesh matmul route (the default single-chip TPU production
    path, incl. the single-indicator self comparison) forced onto CPU via
    the env knob + mesh_shape=1 must reproduce the gather-path run."""
    gs, _truth = synthetic
    m = len(gs.names)
    kw = {"S_ani": 0.95, "cov_thresh": 0.1}
    want_ndb, want_labels = greedy_secondary_cluster(gs, None, list(range(m)), pc=1, kw=kw)

    monkeypatch.setenv("DREP_TPU_GREEDY_MATMUL", "1")
    kw_one = {**kw, "mesh_shape": 1}  # pin single device: 8 CPU test devices
    got_ndb, got_labels = greedy_secondary_cluster(gs, None, list(range(m)), pc=1, kw=kw_one)

    np.testing.assert_array_equal(got_labels, want_labels)
    assert len(got_ndb) == len(want_ndb)
    for col in ("ani", "alignment_coverage", "ref_coverage", "querry_coverage"):
        np.testing.assert_allclose(got_ndb.column(col), want_ndb.column(col), atol=1e-6, err_msg=col)


def test_greedy_from_matrices_equals_engine(synthetic):
    """The small-cluster route (batched matrices + host greedy assignment)
    must reproduce the per-cluster greedy engine exactly: same labels,
    same Ndb comparison set and values."""
    from drep_tpu.cluster.engines import secondary_jax_ani
    from drep_tpu.cluster.greedy import greedy_assign_from_matrices

    gs, _truth = synthetic
    kw = {"S_ani": 0.95, "cov_thresh": 0.1}
    # several small "primary clusters": slices of the synthetic set that mix
    # genomes from different planted clusters (so reps + assignments both occur)
    for lo, hi in [(0, 7), (35, 41), (100, 130), (393, 400)]:
        indices = list(range(lo, hi))
        want_ndb, want_labels = greedy_secondary_cluster(gs, None, indices, pc=9, kw=kw)
        ani, cov = secondary_jax_ani(gs, indices)
        got_ndb, got_labels = greedy_assign_from_matrices(gs, indices, 9, kw, ani, cov)
        np.testing.assert_array_equal(got_labels, want_labels, err_msg=str((lo, hi)))
        assert len(got_ndb) == len(want_ndb)
        for col in ("reference", "querry"):
            assert list(got_ndb.column(col)) == list(want_ndb.column(col))
        for col in ("ani", "alignment_coverage", "ref_coverage", "querry_coverage"):
            np.testing.assert_allclose(got_ndb.column(col), want_ndb.column(col), atol=1e-6, err_msg=col)


def test_greedy_small_clusters_ride_the_batched_path(synthetic, monkeypatch):
    """Controller routing: with greedy on, small clusters go through ONE
    batched device call (35k per-cluster greedy invocations at the 100k
    scale were pathologically slow), while the greedy engine is reserved
    for big clusters."""
    import drep_tpu.cluster.controller as ctrl
    from drep_tpu.cluster import dispatch

    gs, _ = synthetic
    calls = {"batched": 0, "engine": 0}
    real_batched = dispatch.get_secondary_batched("jax_ani")

    def counting_batched(*a, **k):
        calls["batched"] += 1
        return real_batched(*a, **k)

    monkeypatch.setitem(dispatch.SECONDARY_BATCHED, "jax_ani", counting_batched)
    import drep_tpu.cluster.greedy as greedy_mod

    real_engine = greedy_mod.greedy_secondary_cluster

    def counting_engine(*a, **k):
        calls["engine"] += 1
        return real_engine(*a, **k)

    monkeypatch.setattr(greedy_mod, "greedy_secondary_cluster", counting_engine)

    import tempfile

    import pandas as pd

    from drep_tpu.workdir import WorkDirectory

    with tempfile.TemporaryDirectory() as td:
        wd = WorkDirectory(td)
        bdb = pd.DataFrame({"genome": gs.names, "location": gs.names})
        from drep_tpu.ingest import _save, sketch_args_snapshot

        _save(wd, gs)
        wd.store_arguments(
            "sketch",
            sketch_args_snapshot(bdb["genome"], gs.k, gs.sketch_size, gs.scale, "splitmix64"),
        )
        cdb = ctrl.d_cluster_wrapper(
            wd, bdb, greedy_secondary_clustering=True, MASH_sketch=gs.sketch_size
        )
    assert calls["batched"] >= 1  # small clusters batched
    assert calls["engine"] == 0  # no per-cluster greedy fan-out
    assert cdb["secondary_cluster"].nunique() >= 20


# --- ISSUE 55: the representative tile sized to the representatives a block meets ---

# the representatives the LAST block meets -> the founders of each block before it
TILE_CASES = {0: [5], 1: [1, 0], 127: [127, 0], 128: [128, 0], 129: [128, 1, 0],
              512: [128] * 4 + [0], 513: [128] * 4 + [1, 0]}


@pytest.mark.parametrize("meets", list(TILE_CASES))
def test_the_representative_tile_is_sized_to_the_representatives_a_block_meets(monkeypatch, meets):
    """Blocks that meet 0, 1, 127, 128, 129, 512 and 513 representatives: the
    sized tiles give the gather route's and the fixed 512-row tile's Ndb and
    labels bit for bit, and the cluster's entry counts what the rule says."""
    import drep_tpu.cluster.greedy as greedy_mod
    from tests._greedy_testlib import (
        assert_same_answers, fixed_tiles, run_engine, sized_tiles, tile_cluster)

    founders = TILE_CASES[meets]
    gs = tile_cluster(founders)
    met = [sum(founders[:b]) for b in range(len(founders))]
    assert met[-1] == meets
    gather_ndb, gather_labels, gather_rec = run_engine(gs)
    monkeypatch.setenv("DREP_TPU_GREEDY_MATMUL", "1")
    ndb, labels, rec = run_engine(gs)
    monkeypatch.setattr(greedy_mod, "_rep_tile_rows", fixed_tiles)
    fixed_ndb, fixed_labels, fixed_rec = run_engine(gs)

    assert len(set(labels)) == sum(founders) and len(ndb) > 0  # every stranger founds a group
    assert_same_answers(ndb, labels, gather_ndb, gather_labels)
    assert_same_answers(ndb, labels, fixed_ndb, fixed_labels)

    (call,), (fixed_call,), (gather_call,) = (r["secondary_greedy_calls"] for r in (rec, fixed_rec, gather_rec))
    tiles = [sized_tiles(n) for n in met]
    assert call["chunks"] == 1 and call["rep_tile"] == 512  # the largest tile
    assert call["blocks"] == len(founders) and call["rep_rows_real"] == sum(met)
    assert call["rep_rows_shipped"] == sum(map(sum, tiles))
    assert call["device_calls"] == sum(len(t) + 1 for t in tiles)
    assert call["blocks_without_reps"] == 1  # the first block of a cluster
    # the rule before: whole tiles of 512, one at the least, and a call for every block
    assert fixed_call["rep_rows_shipped"] == sum(max(-(-n // 512), 1) * 512 for n in met)
    assert fixed_call["device_calls"] == sum(max(-(-n // 512), 1) + 1 for n in met)
    assert fixed_call["blocks_without_reps"] == 0 == gather_call["blocks_without_reps"]
    assert gather_call["rep_rows_shipped"] == sum(max(-(-n // 128), 1) * 128 for n in met)
    # what reached the device does not depend on the tiles: every row once
    assert call["id_slots"] == fixed_call["id_slots"] and call["bytes_shipped"] == fixed_call["bytes_shipped"]
    assert rec["phases"]["secondary/greedy_wait"]["calls"] == len(founders)  # still a span a block


@pytest.mark.parametrize("n_reps,tiles", [
    (0, []), (1, [128]), (127, [128]), (128, [128]), (129, [256]), (256, [256]), (257, [512]),
    (512, [512]), (513, [512, 128]), (1024, [512, 512]), (1300, [512, 512, 512]),
])
def test_the_tile_rule_is_one_function_of_the_representatives_and_the_unscaled_block(n_reps, tiles):
    from drep_tpu.cluster.greedy import _rep_tile_rows
    from tests._greedy_testlib import sized_tiles

    assert _rep_tile_rows(n_reps, 128) == tiles == sized_tiles(n_reps)
    assert _rep_tile_rows(n_reps, 64) == [t // 2 for t in _rep_tile_rows(2 * n_reps, 128)]

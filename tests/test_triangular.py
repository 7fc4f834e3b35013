"""Triangle-only all-pairs schedules (ISSUE 1) vs their full-grid
references, on the 8-device virtual CPU mesh.

Every dense compare engine exploits output symmetry: the half-ring
(parallel/allpairs.py), the blocked upper-triangle matmuls
(ops/containment.py), and the tiled searchsorted fallback. Each
triangular path must be EXACTLY equal (same float32 bits) to its full-grid
twin — the mirrored blocks are transposed copies of
bit-identical symmetric payloads — and the profiling counters must prove
the triangular schedule engaged (tiles_computed well under tiles_total).
"""

import jax
import numpy as np
import pandas as pd
import pytest

from drep_tpu.ops.containment import (
    all_vs_all_containment,
    all_vs_all_containment_matmul,
    all_vs_all_containment_matmul_chunked,
    pack_scaled_sketches,
)
from drep_tpu.ops.minhash import all_vs_all_mash, pack_sketches
from drep_tpu.parallel.allpairs import (
    half_ring_steps,
    sharded_containment_allpairs,
    sharded_mash_allpairs,
)
from drep_tpu.parallel.mesh import make_mesh
from drep_tpu.utils.profiling import counters


def _sketch_set(rng, n, s):
    base = np.unique(rng.integers(0, 2**62, size=6 * s * n, dtype=np.uint64))
    rng.shuffle(base)
    shared = base[:s]
    out = []
    for i in range(n):
        own = base[s * (i + 1) : s * (i + 2)]
        mix = int(s * rng.random() * 0.8)
        out.append(np.sort(np.unique(np.concatenate([shared[:mix], own[: s - mix]]))[:s]))
    return out


def _tile_diff(stage: str):
    st = counters.stages.get(stage)
    return (st.tiles_computed, st.tiles_total) if st else (0, 0)


# odd and even device counts: the even-D half ring has the split middle
# step, the odd-D one does not — both schedules must cover every pair
@pytest.mark.parametrize("n_dev", [3, 8])
def test_ring_mash_triangular_equals_full(rng, n_dev):
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual CPU devices"
    mesh = make_mesh(n_dev)
    n = 21  # not a device multiple: exercises padding under the mirror
    s = 64
    packed = pack_sketches(_sketch_set(rng, n, s), [f"g{i}" for i in range(n)], s)

    tc0, tt0 = _tile_diff("primary_compare")
    tri = sharded_mash_allpairs(packed, k=21, mesh=mesh)
    tc1, tt1 = _tile_diff("primary_compare")
    full = sharded_mash_allpairs(packed, k=21, mesh=mesh, full_grid=True)

    # exact float32 equality: the mash tile is symmetric bit-for-bit, the
    # mirror copies transposes — no estimator drift allowed
    np.testing.assert_array_equal(tri, full)
    dense, _ = all_vs_all_mash(packed, k=21, tile=8)
    assert np.allclose(tri, dense, atol=1e-6)

    # counters prove the triangular schedule engaged: D*(D+1)/2 of D^2
    assert (tc1 - tc0, tt1 - tt0) == (n_dev * (n_dev + 1) // 2, n_dev * n_dev)
    assert (tc1 - tc0) / (tt1 - tt0) <= (n_dev + 1) / (2 * n_dev)
    assert half_ring_steps(n_dev) == n_dev // 2 + 1


@pytest.mark.parametrize("n_dev", [3, 8])
def test_ring_containment_triangular_equals_full(rng, n_dev):
    mesh = make_mesh(n_dev)
    n = 19
    packed = pack_scaled_sketches(
        _sketch_set(rng, n, 96), [f"g{i}" for i in range(n)], pad_multiple=32
    )

    tc0, tt0 = _tile_diff("secondary_compare")
    tri_ani, tri_cov = sharded_containment_allpairs(packed, k=21, mesh=mesh)
    tc1, tt1 = _tile_diff("secondary_compare")
    full_ani, full_cov = sharded_containment_allpairs(
        packed, k=21, mesh=mesh, full_grid=True
    )

    np.testing.assert_array_equal(tri_ani, full_ani)
    np.testing.assert_array_equal(tri_cov, full_cov)
    # the ring ships symmetric raw intersections; both DIRECTIONAL cov
    # sides derived on host must match the dense searchsorted path exactly
    dense_ani, dense_cov = all_vs_all_containment(packed, k=21, tile=8)
    np.testing.assert_array_equal(tri_ani, dense_ani)
    np.testing.assert_array_equal(tri_cov, dense_cov)

    assert (tc1 - tc0, tt1 - tt0) == (n_dev * (n_dev + 1) // 2, n_dev * n_dev)


def test_single_chip_tile_fraction_at_most_55_percent(rng):
    """The blocked single-chip schedules clear the <= ~55% pair-tile bar
    once the grid has >= 10 block rows (the ratio is (B+1)/(2B))."""
    n, s = 60, 32
    packed = pack_sketches(_sketch_set(rng, n, s), [f"g{i}" for i in range(n)], s)
    tc0, tt0 = _tile_diff("primary_compare")
    all_vs_all_mash(packed, k=21, tile=4)  # 15 block rows
    tc1, tt1 = _tile_diff("primary_compare")
    assert (tc1 - tc0, tt1 - tt0) == (15 * 16 // 2, 15 * 15)
    assert (tc1 - tc0) / (tt1 - tt0) <= 0.55

    n = 80
    packed_s = pack_scaled_sketches(
        _sketch_set(rng, n, 64), [f"g{i}" for i in range(n)], pad_multiple=32
    )
    tc0, tt0 = _tile_diff("secondary_compare")
    all_vs_all_containment(packed_s, k=21, tile=8)  # 10 block rows
    tc1, tt1 = _tile_diff("secondary_compare")
    assert (tc1 - tc0, tt1 - tt0) == (10 * 11 // 2, 10 * 10)
    assert (tc1 - tc0) / (tt1 - tt0) <= 0.55


# odd and even device counts: the even-D middle step's canonical-half
# filter moves from a device-side jnp.where (monolithic) to a host-side
# store decision (step-wise) — both must cover every pair identically
@pytest.mark.parametrize("n_dev", [3, 8])
def test_stepwise_ring_equals_monolithic_bit_exact(rng, n_dev):
    """The host-stepped elastic ring (ISSUE 4) against the monolithic
    single-program reference: same mesh, same schedule, EXACT float32
    equality for both kernel kinds — the per-step dispatch, the host
    assembly from per-device shards, and the mirror must not move a
    single ulp. Also pins the per-BLOCK recovery unit: a standalone
    recompute of one block is bit-identical to its in-ring twin (the
    elastic re-deal depends on it)."""
    from drep_tpu.parallel.allpairs import (
        _block_tile_fn,
        configure_ring,
        ring_schedule,
    )

    configure_ring()  # hermetic: no store base leaked from earlier tests
    mesh = make_mesh(n_dev)
    n, s = 21, 64
    packed = pack_sketches(_sketch_set(rng, n, s), [f"g{i}" for i in range(n)], s)
    sw = sharded_mash_allpairs(packed, k=21, mesh=mesh)
    mono = sharded_mash_allpairs(packed, k=21, mesh=mesh, monolithic=True)
    assert sw.tobytes() == mono.tobytes(), "step-wise mash ring != monolithic"

    nc = 19
    packed_c = pack_scaled_sketches(
        _sketch_set(rng, nc, 96), [f"c{i}" for i in range(nc)], pad_multiple=32
    )
    a_sw, c_sw = sharded_containment_allpairs(packed_c, k=21, mesh=mesh)
    a_mono, c_mono = sharded_containment_allpairs(
        packed_c, k=21, mesh=mesh, monolithic=True
    )
    assert a_sw.tobytes() == a_mono.tobytes()
    assert c_sw.tobytes() == c_mono.tobytes()

    # the recovery unit: recompute one schedule block standalone and
    # compare against the assembled matrix's block — bit-for-bit
    from drep_tpu.ops.minhash import pad_packed_rows

    ids, counts = pad_packed_rows(packed.ids, packed.counts, n_dev)
    n_local = ids.shape[0] // n_dev
    tile_jit, _ = _block_tile_fn("mash", 21)
    a, b = ring_schedule(n_dev, half=True)[1]
    asl = slice(a * n_local, (a + 1) * n_local)
    bsl = slice(b * n_local, (b + 1) * n_local)
    (blk,) = tile_jit(ids[asl], counts[asl], ids[bsl], counts[bsl])
    full = np.zeros((ids.shape[0], ids.shape[0]), np.float32)
    full[: n, : n] = sw
    if a * n_local != b * n_local:  # off-diagonal: no fill_diagonal overlap
        assert np.asarray(blk)[: min(n_local, n - a * n_local), :].tobytes() == (
            full[asl, bsl][: min(n_local, n - a * n_local), :].tobytes()
        )


def test_the_reference_ring_is_reached_by_argument_alone(rng, monkeypatch):
    """The monolithic ring is the tests' reference, an argument of the
    function: the environment variable that once selected it steers
    nothing now — a step-wise ring under it books the step-wise
    schedule's counters and returns the same bytes."""
    from drep_tpu.parallel.allpairs import configure_ring, ring_tiles_computed

    configure_ring()  # hermetic: no store base leaked from earlier tests
    n_dev = 4
    mesh = make_mesh(n_dev)
    packed = pack_sketches(_sketch_set(rng, 21, 64), [f"g{i}" for i in range(21)], 64)

    def steps_booked():
        ph = counters.phases.get(("ring_step", True))
        return ph.calls if ph else 0

    def ring(**how):
        s0, t0 = steps_booked(), _tile_diff("primary_compare")[0]
        out = sharded_mash_allpairs(packed, k=21, mesh=mesh, **how)
        return out, steps_booked() - s0, _tile_diff("primary_compare")[0] - t0

    plain, plain_steps, plain_tiles = ring()
    # drep-lint: allow[env-knob] — the retired name, set to show that nothing reads it
    monkeypatch.setenv("DREP_TPU_RING_MONOLITHIC", "1")
    under, under_steps, under_tiles = ring()
    assert under.tobytes() == plain.tobytes()
    # a `ring_step` span a host-dispatched step; the one program books none
    assert under_steps == plain_steps == half_ring_steps(n_dev)
    assert under_tiles == plain_tiles == ring_tiles_computed(n_dev, half=True)
    mono, mono_steps, _ = ring(monolithic=True)
    assert mono_steps == 0
    assert mono.tobytes() == plain.tobytes()


# the edge shapes of the ring's blocks: one row a device, an empty shard
# (a device whose whole block is padding), a single genome
@pytest.mark.parametrize("n_genomes", ["n_dev", "n_dev-1", "1"])
@pytest.mark.parametrize("n_dev", [3, 8])
@pytest.mark.parametrize("kind", ["mash", "containment"])
def test_ring_edge_shapes_equal_single_device_tiles(rng, kind, n_dev, n_genomes):
    from drep_tpu.parallel.allpairs import configure_ring

    configure_ring()  # hermetic: no store base leaked from earlier tests
    mesh = make_mesh(n_dev)
    n = {"n_dev": n_dev, "n_dev-1": n_dev - 1, "1": 1}[n_genomes]
    names = [f"g{i}" for i in range(n)]
    f0 = counters.faults.get("ring_step_failures", 0)
    r0 = counters.faults.get("ring_blocks_recovered", 0)
    if kind == "mash":
        packed = pack_sketches(_sketch_set(rng, n, 32), names, 32)
        got = (sharded_mash_allpairs(packed, k=21, mesh=mesh),)
        want = all_vs_all_mash(packed, k=21, tile=8)[:1]
    else:
        packed = pack_scaled_sketches(_sketch_set(rng, n, 96), names, pad_multiple=32)
        got = sharded_containment_allpairs(packed, k=21, mesh=mesh)
        want = all_vs_all_containment(packed, k=21, tile=8)
    for g, w in zip(got, want):
        assert g.shape == (n, n)
        np.testing.assert_array_equal(g, w)
    # the ring's own steps made the blocks: the per-block recovery path
    # gives the same bits, so a ring that never ran would pass without this
    assert counters.faults.get("ring_step_failures", 0) == f0
    assert counters.faults.get("ring_blocks_recovered", 0) == r0


def test_ring_step_autotimeout_excludes_first_step_only():
    """The ring's per-step AutoTimeout excludes exactly the FIRST (cold)
    step from the rolling median — the TileExecutor-style warmup
    exclusion resized for half-ring schedules (a warmup of 8 would
    discard every sample at production D and the gauge never derive)."""
    from drep_tpu.parallel.allpairs import RING_STEP_WARMUP
    from drep_tpu.parallel.faulttol import (
        AUTO_TIMEOUT_FLOOR_S,
        AutoTimeout,
        FaultTolConfig,
    )

    assert RING_STEP_WARMUP == 1
    auto = AutoTimeout(FaultTolConfig(auto_timeout=True), warmup=RING_STEP_WARMUP)
    auto.note(500.0)  # the cold step must not poison the median
    for _ in range(4):
        auto.note(0.01)  # the D=8 half-ring's warm steps
    derived = auto.derived()
    assert derived is not None, "gauge must derive from a half-ring schedule"
    assert derived == AUTO_TIMEOUT_FLOOR_S  # 20x median(0.01) floors at 30s
    # default warmup (the TileExecutor) still excludes its 8
    auto_default = AutoTimeout(FaultTolConfig(auto_timeout=True))
    for _ in range(5):
        auto_default.note(0.01)
    assert auto_default.derived() is None


def test_containment_matmul_triangular_equals_full(rng):
    n = 70
    packed = pack_scaled_sketches(
        _sketch_set(rng, n, 96), [f"g{i}" for i in range(n)], pad_multiple=32
    )
    a_tri, c_tri = all_vs_all_containment_matmul(packed, k=21)
    a_full, c_full = all_vs_all_containment_matmul(packed, k=21, triangular=False)
    np.testing.assert_array_equal(a_tri, a_full)
    np.testing.assert_array_equal(c_tri, c_full)
    # the searchsorted fallback and the vocab-chunked path land on the
    # same integers, so the whole family stays bit-equal
    a_ss, c_ss = all_vs_all_containment(packed, k=21, tile=8)
    np.testing.assert_array_equal(a_tri, a_ss)
    np.testing.assert_array_equal(c_tri, c_ss)
    a_ch, c_ch = all_vs_all_containment_matmul_chunked(packed, k=21)
    np.testing.assert_array_equal(a_ch, a_tri)
    np.testing.assert_array_equal(c_ch, c_tri)


def test_dense_pair_totals_match_streaming_convention(rng):
    """Perf guard: the pair totals recorded for the dense engines are the
    N*(N-1)/2 UNIQUE pairs — mirroring the triangle into a full [N, N]
    matrix must not double them — matching streaming's pairs_computed."""
    from drep_tpu.cluster.controller import _fill_defaults, _primary_clusters
    from drep_tpu.ingest import GenomeSketches
    from drep_tpu.parallel.streaming import streaming_mash_edges

    n, s = 24, 64
    sketches = _sketch_set(rng, n, s)
    names = [f"g{i}" for i in range(n)]
    gdb = pd.DataFrame(
        {
            "genome": names,
            "length": np.full(n, 1_000_000, np.int64),
            "N50": np.full(n, 50_000, np.int64),
            "contigs": np.full(n, 10, np.int64),
            "n_kmers": np.full(n, 900_000, np.int64),
        }
    )
    gs = GenomeSketches(
        names=names, gdb=gdb, bottom=sketches, scaled=sketches,
        k=21, sketch_size=s, scale=200,
    )
    bdb = pd.DataFrame({"genome": names, "location": names})
    kw = _fill_defaults({})
    _labels, _dist, _link, _mdb, pairs_done = _primary_clusters(gs, bdb, kw)
    assert pairs_done == n * (n - 1) // 2  # what controller records as pairs

    packed = pack_sketches(sketches, names, s)
    _ii, _jj, _dd, pairs_streaming = streaming_mash_edges(
        packed, k=21, cutoff=1.0, block=8, use_pallas=False
    )
    assert pairs_streaming == pairs_done

"""Fused Pallas DMA ring (ISSUE 8) vs the ppermute reference.

The fused rotate+compare kernel (ops/pallas_ring.py) must be a drop-in
for the step-wise ring's rotating steps: block tiles BIT-IDENTICAL to
the lax.ppermute schedule at odd and even D (the even-D half ring has
the split middle step and the rotate-last-skip), double-buffer rotation
correct across chained steps, checkpoint shards byte-compatible across
comm backends. The compiled kernel is off the default dispatch ('auto'
is ppermute); an explicit 'pallas_dma' is honored and raises what the
compiler says. Every equality pin also requires that NO ring step failed
and NO block was recovered: the per-block recovery path produces the same
bits, so without that a kernel that never ran would still pass.
"""

import os

import jax
import numpy as np
import pytest

from drep_tpu.ops.containment import pack_scaled_sketches
from drep_tpu.ops.minhash import pack_sketches, pad_packed_rows
from drep_tpu.parallel.allpairs import (
    RING_COMM_CHOICES,
    configure_ring,
    resolve_ring_comm,
    ring_comm_requested,
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


@pytest.fixture(autouse=True)
def _hermetic_ring_config():
    configure_ring()
    counters.reset()
    yield
    configure_ring()


def _assert_fused_ran_clean():
    """The fused kernel itself produced the blocks: the gauge is set from
    a step that RAN, and nothing went through per-block recovery."""
    assert counters.gauges.get("ring_comm_pallas") == 1.0
    assert counters.faults.get("ring_step_failures", 0) == 0
    assert counters.faults.get("ring_blocks_recovered", 0) == 0


# odd and even device counts: even D exercises the split middle step and
# a different rotate-last-skip position — both schedules must produce
# bit-identical matrices under the fused kernel
@pytest.mark.parametrize("n_dev", [3, 8])
def test_fused_mash_ring_bit_equals_ppermute(rng, n_dev):
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual CPU devices"
    mesh = make_mesh(n_dev)
    n, s = 21, 64
    packed = pack_sketches(_sketch_set(rng, n, s), [f"g{i}" for i in range(n)], s)
    want = sharded_mash_allpairs(packed, k=21, mesh=mesh, ring_comm="ppermute")
    got = sharded_mash_allpairs(packed, k=21, mesh=mesh, ring_comm="pallas_interpret")
    assert got.tobytes() == want.tobytes(), "fused pallas ring != ppermute ring"
    _assert_fused_ran_clean()


@pytest.mark.parametrize("n_dev", [3, 8])
def test_fused_containment_ring_bit_equals_ppermute(rng, n_dev):
    mesh = make_mesh(n_dev)
    n = 19
    packed = pack_scaled_sketches(
        _sketch_set(rng, n, 96), [f"g{i}" for i in range(n)], pad_multiple=32
    )
    a_w, c_w = sharded_containment_allpairs(packed, k=21, mesh=mesh, ring_comm="ppermute")
    a_g, c_g = sharded_containment_allpairs(
        packed, k=21, mesh=mesh, ring_comm="pallas_interpret"
    )
    assert a_g.tobytes() == a_w.tobytes()
    assert c_g.tobytes() == c_w.tobytes()
    _assert_fused_ran_clean()


def test_double_buffer_rotation_across_chained_steps(rng):
    """Step i's B output feeds step i+1's B input (the host-threaded
    double-buffer swap): after j chained fused steps every device must
    hold the block j hops upstream — exactly j applications of the
    ppermute perm [(m, (m+1) % D)] — while each step's tile matches the
    one the resident operands predict."""
    from drep_tpu.ops.minhash import mash_distance_tile
    from drep_tpu.ops.pallas_ring import fused_ring_step_fn
    from drep_tpu.parallel.allpairs import put_global
    from jax.sharding import NamedSharding, PartitionSpec as P

    from drep_tpu.parallel.mesh import AXIS

    D, n = 4, 16
    s = 32
    mesh = make_mesh(D)
    packed = pack_sketches(_sketch_set(rng, n, s), [f"g{i}" for i in range(n)], s)
    ids, cts = pad_packed_rows(packed.ids, packed.counts, D)
    n_local = ids.shape[0] // D
    ids_d = put_global(ids, NamedSharding(mesh, P(AXIS, None)))
    cts_d = put_global(cts, NamedSharding(mesh, P(AXIS)))
    fn, _ = fused_ring_step_fn("mash", 21, mesh, interpret=True)

    b_ids, b_cts = ids_d, cts_d
    for step in range(1, D):
        tile, b_ids, b_cts = fn(ids_d, cts_d, b_ids, b_cts)
        # rotation: device m now holds block (m - step) mod D
        want_ids = np.roll(
            ids.reshape(D, n_local, s), step, axis=0
        ).reshape(D * n_local, s)
        assert np.asarray(b_ids).tobytes() == want_ids.tobytes(), step
        want_cts = np.roll(cts.reshape(D, n_local), step, axis=0).ravel()
        assert np.asarray(b_cts).tobytes() == want_cts.tobytes(), step
        # the tile was computed from the PRE-rotation operand (the overlap
        # contract: compute rides the buffer the DMA is draining)
        pre = np.roll(ids.reshape(D, n_local, s), step - 1, axis=0).reshape(-1, s)
        pre_c = np.roll(cts.reshape(D, n_local), step - 1, axis=0).ravel()
        for m in range(D):
            sl = slice(m * n_local, (m + 1) * n_local)
            d_want, _ = mash_distance_tile(
                ids[sl], cts[sl], pre[sl], pre_c[sl], k=21
            )
            assert (
                np.asarray(tile)[sl].tobytes()
                == np.asarray(d_want).astype(np.float32).tobytes()
            ), (step, m)


def test_checkpoint_shards_are_comm_backend_agnostic(rng, tmp_path):
    """A store written by the FUSED ring must resume under the ppermute
    ring (and vice versa) with zero recompute and bit-identical output —
    per-step blk shards are the redoable unit from PR 4 and the comm
    backend must not leak into them."""
    mesh = make_mesh(3)
    n, s = 21, 64
    packed = pack_sketches(_sketch_set(rng, n, s), [f"g{i}" for i in range(n)], s)
    ckpt = str(tmp_path / "ring")
    want = sharded_mash_allpairs(
        packed, k=21, mesh=mesh, checkpoint_dir=ckpt, ring_comm="pallas_interpret"
    )
    shards = sorted(f for f in os.listdir(ckpt) if f.startswith("blk_"))
    assert len(shards) == 3 * 4 // 2, shards
    _assert_fused_ran_clean()
    tc0 = counters.stages["primary_compare"].tiles_computed
    got = sharded_mash_allpairs(
        packed, k=21, mesh=mesh, checkpoint_dir=ckpt, ring_comm="ppermute"
    )
    # full resume: the ppermute run computed NOTHING, every block loaded
    assert counters.stages["primary_compare"].tiles_computed == tc0
    assert got.tobytes() == want.tobytes()
    # the backend gauge is honest on resume too: no fused step ran in the
    # second call, whatever the first call's backend was
    assert counters.gauges.get("ring_comm_pallas") == 0.0


def test_auto_is_ppermute_and_explicit_requests_are_honored():
    """The compiled fused kernel is off the default dispatch: 'auto'
    resolves to ppermute. Explicit requests resolve to themselves — no
    self-check swaps them for another backend."""
    mesh = make_mesh(3)
    assert resolve_ring_comm(mesh, "auto") == "ppermute"
    assert resolve_ring_comm(mesh, "ppermute") == "ppermute"
    assert resolve_ring_comm(mesh, "pallas_dma") == "pallas_dma"
    assert resolve_ring_comm(mesh, "pallas_interpret") == "pallas_interpret"
    assert resolve_ring_comm(make_mesh(1), "pallas_dma") == "ppermute"  # nothing to rotate


def test_explicit_pallas_dma_that_cannot_build_raises(rng):
    """A step program that does not build ends the run: the compiled
    kernel cannot compile off-TPU, and asking for it must raise the
    compiler's error — not run ppermute, not "recover" every block."""
    mesh = make_mesh(3)
    n, s = 12, 32
    packed = pack_sketches(_sketch_set(rng, n, s), [f"g{i}" for i in range(n)], s)
    with pytest.raises(Exception, match="(?i)interpret|cpu|pallas"):
        sharded_mash_allpairs(packed, k=21, mesh=mesh, ring_comm="pallas_dma")
    assert counters.gauges.get("ring_comm_pallas") == 0.0
    assert not counters.faults, counters.faults


def test_bad_comm_validation(monkeypatch):
    monkeypatch.setenv("DREP_TPU_RING_COMM", "warp_drive")
    with pytest.raises(ValueError, match="warp_drive"):
        ring_comm_requested()
    monkeypatch.setenv("DREP_TPU_RING_COMM", "pallas_interpret")
    assert ring_comm_requested() == "pallas_interpret"
    assert set(RING_COMM_CHOICES) == {
        "auto", "ppermute", "pallas_dma", "pallas_interpret"
    }


def test_fused_ring_tile_sizing():
    """ISSUE 16: the block-size REFUSAL is gone — every shape gets a
    tile, never a verdict. Bench-scale blocks run un-gridded (tile ==
    n_local); the 100k-genome/D=16 primary block the old
    `fused_block_fits` refused now grids down until its per-cell working
    set fits the `DREP_TPU_RING_VMEM_MB` budget; a starved budget floors
    at single-row tiles instead of refusing."""
    from drep_tpu.ops.pallas_ring import fused_ring_tile

    assert fused_ring_tile(128, 256) == 128
    assert fused_ring_tile(256, 1024) == 256
    big = fused_ring_tile(6250, 1024)  # the block the old gate refused
    assert 1 <= big < 6250
    # sized against the budget: pipeline-double-buffered slabs + tiles fit
    assert 2 * (2 * (big * 1024 * 4 + big * 4) + big * big * 4) <= 12 << 20
    assert fused_ring_tile(6250, 1024, vmem_mb=1) < big  # knob shrinks tiles
    assert fused_ring_tile(4096, 4096, vmem_mb=0) == 1  # floor, not refusal
    assert fused_ring_tile(1, 64) == 1  # single-row block


@pytest.mark.parametrize("n_dev", [3, 8])
def test_gridded_fused_ring_nondivisible_and_single_row(rng, n_dev, monkeypatch):
    """Grid-edge shapes (ISSUE 16): a VMEM budget small enough to force
    multi-tile grids with a RAGGED last block (n_local not divisible by
    the tile), and a D-sized input that pads to single-row blocks — both
    bit-identical to the ppermute reference."""
    monkeypatch.setenv("DREP_TPU_RING_VMEM_MB", "0")  # tile floor: 1 row
    mesh = make_mesh(n_dev)
    n, s = 21, 64
    packed = pack_sketches(_sketch_set(rng, n, s), [f"g{i}" for i in range(n)], s)
    want = sharded_mash_allpairs(packed, k=21, mesh=mesh, ring_comm="ppermute")
    got = sharded_mash_allpairs(packed, k=21, mesh=mesh, ring_comm="pallas_interpret")
    assert got.tobytes() == want.tobytes(), "gridded fused ring != ppermute ring"
    _assert_fused_ran_clean()
    # single-row blocks: exactly D genomes -> n_local == 1
    small = pack_sketches(
        _sketch_set(rng, n_dev, 32), [f"s{i}" for i in range(n_dev)], 32
    )
    want1 = sharded_mash_allpairs(small, k=21, mesh=mesh, ring_comm="ppermute")
    got1 = sharded_mash_allpairs(small, k=21, mesh=mesh, ring_comm="pallas_interpret")
    assert got1.tobytes() == want1.tobytes()
    _assert_fused_ran_clean()


@pytest.mark.parametrize("n_dev", [3, 8])
def test_gridded_fused_ring_past_old_vmem_cap(rng, n_dev):
    """The acceptance pin: a block whose working set exceeds the old
    12 MB single-shot cap (a shape `fused_block_fits` used to refuse)
    streams through the gridded kernel bit-identical to ppermute at odd
    and even D. 1792 rows per device: the [n_local, n_local] f32 output
    tile alone is ~12.85 MB (> 12 MB) — it is the OUTPUT tile that
    bursts the old cap, so the sketches stay at the narrowest width
    (s=2) to keep the D=8 CPU merge compute tier-1-sized; merge-width
    coverage lives in the other parity pins (s=64 ragged, s=96 MXU)."""
    from drep_tpu.ops.pallas_ring import fused_ring_tile

    mesh = make_mesh(n_dev)
    n_local, s = 1792, 2
    n = n_dev * n_local
    # the OLD single-shot working set (2 operands + f32 tile + counts)
    # exceeds the deleted 12 MB cap — this exact shape used to refuse
    assert 2 * (n_local * s * 4) + n_local * n_local * 4 + n_local * 8 > 12 << 20
    assert fused_ring_tile(n_local, s) < n_local  # the grid actually engages
    rng2 = np.random.default_rng(7)
    ids = np.sort(rng2.integers(0, 2**30, size=(n, s), dtype=np.int32), axis=1)
    cts = np.full(n, s, np.int32)
    from drep_tpu.ops.minhash import PackedSketches

    packed = PackedSketches(ids=ids, counts=cts, names=[f"g{i}" for i in range(n)])
    want = sharded_mash_allpairs(packed, k=21, mesh=mesh, ring_comm="ppermute")
    got = sharded_mash_allpairs(packed, k=21, mesh=mesh, ring_comm="pallas_interpret")
    assert got.tobytes() == want.tobytes(), "past-cap gridded ring != ppermute"
    _assert_fused_ran_clean()


@pytest.mark.parametrize("n_dev", [3, 8])
def test_mxu_matmul_variant_ring_bit_equals_ppermute(rng, n_dev, monkeypatch):
    """The MXU intersection-matmul variant (the Mosaic escape hatch) must
    pass the SAME equality pin as the merge network: containment ring
    under `DREP_TPU_RING_VARIANT=matmul`, gridded (starved VMEM budget),
    bit-identical to the ppermute reference."""
    monkeypatch.setenv("DREP_TPU_RING_VARIANT", "matmul")
    monkeypatch.setenv("DREP_TPU_RING_VMEM_MB", "0")
    mesh = make_mesh(n_dev)
    n = 19
    packed = pack_scaled_sketches(
        _sketch_set(rng, n, 96), [f"g{i}" for i in range(n)], pad_multiple=32
    )
    a_w, c_w = sharded_containment_allpairs(packed, k=21, mesh=mesh, ring_comm="ppermute")
    a_g, c_g = sharded_containment_allpairs(
        packed, k=21, mesh=mesh, ring_comm="pallas_interpret"
    )
    assert a_g.tobytes() == a_w.tobytes(), "matmul-variant ring != ppermute"
    assert c_g.tobytes() == c_w.tobytes()
    _assert_fused_ran_clean()


def test_mxu_matmul_tile_equals_merge_tile(rng):
    """Property pin: on the SAME device-resident operands, one fused step
    with the matmul tile variant produces byte-identical output (tile AND
    rotated operands) to the merge-network variant — the per-tile
    equivalence the escape hatch rests on, across ragged grids and
    several vocab extents (forcing 1..many vocab chunks)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from drep_tpu.ops.pallas_ring import fused_ring_step_fn, matmul_ring_vocab_pad
    from drep_tpu.parallel.allpairs import put_global
    from drep_tpu.parallel.mesh import AXIS

    D = 3
    mesh = make_mesh(D)
    for n_local, s, vocab in [(5, 32, 200), (8, 64, 9000), (1, 16, 100)]:
        n = D * n_local
        ids = np.full((n, s), 2**31 - 1, np.int32)
        for i in range(n):
            ln = int(rng.integers(1, s + 1))
            ids[i, :ln] = np.sort(
                rng.choice(vocab, size=ln, replace=False).astype(np.int32)
            )
        cts = np.minimum((ids != 2**31 - 1).sum(1), s).astype(np.int32)
        ids_d = put_global(ids, NamedSharding(mesh, P(AXIS, None)))
        cts_d = put_global(cts, NamedSharding(mesh, P(AXIS)))
        v_pad = matmul_ring_vocab_pad(ids)
        merge_fn, _ = fused_ring_step_fn("containment", 21, mesh, interpret=True)
        mm_fn, _ = fused_ring_step_fn(
            "containment", 21, mesh, interpret=True, variant="matmul", v_pad=v_pad
        )
        t_m, bi_m, bc_m = merge_fn(ids_d, cts_d, ids_d, cts_d)
        t_x, bi_x, bc_x = mm_fn(ids_d, cts_d, ids_d, cts_d)
        case = (n_local, s, vocab)
        assert np.asarray(t_x).tobytes() == np.asarray(t_m).tobytes(), case
        assert np.asarray(bi_x).tobytes() == np.asarray(bi_m).tobytes(), case
        assert np.asarray(bc_x).tobytes() == np.asarray(bc_m).tobytes(), case


def test_matmul_variant_validation(monkeypatch):
    """The matmul variant is containment-only (mash's tile counts shared
    ids within the union bottom-s, not plain |A∩B|) and demands a static
    pow2 v_pad; the variant pin never reaches merge-only kinds."""
    from drep_tpu.ops.pallas_ring import fused_ring_step_fn, fused_ring_variant

    mesh = make_mesh(2)
    with pytest.raises(ValueError, match="matmul ring variant supports"):
        fused_ring_step_fn("mash", 21, mesh, interpret=True, variant="matmul", v_pad=256)
    with pytest.raises(ValueError, match="v_pad"):
        fused_ring_step_fn(
            "containment", 21, mesh, interpret=True, variant="matmul", v_pad=0
        )
    assert fused_ring_variant("containment") == "merge"  # the default
    monkeypatch.setenv("DREP_TPU_RING_VARIANT", "matmul")
    assert fused_ring_variant("containment") == "matmul"
    assert fused_ring_variant("mash") == "merge"  # never matmul, any pin
    monkeypatch.setenv("DREP_TPU_RING_VARIANT", "auto")
    with pytest.raises(ValueError, match="DREP_TPU_RING_VARIANT"):
        fused_ring_variant("containment")


def test_ring_comm_gauge_reports_ppermute(rng):
    mesh = make_mesh(3)
    n, s = 12, 32
    packed = pack_sketches(_sketch_set(rng, n, s), [f"g{i}" for i in range(n)], s)
    sharded_mash_allpairs(packed, k=21, mesh=mesh, ring_comm="ppermute")
    assert counters.gauges.get("ring_comm_pallas") == 0.0


def test_ring_step_autotimeout_excludes_first_step_only():
    """ISSUE 8 satellite: the ring's per-step AutoTimeout excludes
    exactly the FIRST (compile-bearing) step from the rolling median —
    the TileExecutor-style warmup exclusion resized for half-ring
    schedules (the old warmup of 8 discarded every sample at production
    D and the gauge never derived)."""
    from drep_tpu.parallel.allpairs import RING_STEP_WARMUP
    from drep_tpu.parallel.faulttol import (
        AUTO_TIMEOUT_FLOOR_S,
        AutoTimeout,
        FaultTolConfig,
    )

    assert RING_STEP_WARMUP == 1
    auto = AutoTimeout(FaultTolConfig(auto_timeout=True), warmup=RING_STEP_WARMUP)
    auto.note(500.0)  # the cold step: compile-inflated, must not poison
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

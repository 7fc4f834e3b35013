"""Gridded fused rotate+compare ring step — a Pallas TPU kernel (ISSUE 8/16).

The host-stepped dense ring loses time to dispatch gaps between
`shard_map` programs and to `lax.ppermute` rotations that serialize
against the compare kernel (XLA schedules the collective after the tile
compute that consumes the SAME b operand — the transfer and the compute
never overlap); how much, on real chips, is not measured (ROADMAP S7).
This module fuses the two into ONE `pallas_call` per
ring step (SNIPPETS.md [1]/[2], the JAX Pallas TPU distributed-guide
pattern): the kernel STARTS an async remote copy of the local B operand
to the ring neighbor's receive buffer (`pltpu.make_async_remote_copy`,
DMA semaphores in scratch, `device_id_type=MESH`), computes the step's
tiles from the still-resident B block while the ICI transfer is in
flight, then WAITS the semaphores — rotation hidden entirely behind
compute.

GRIDDING (ISSUE 16): the PR 8 kernel was single-shot — both whole
operands pinned in VMEM — and `fused_block_fits` refused any block past
a 12 MB working set, so production-size blocks always fell back to
ppermute. The step is now a
`pallas_call` grid over (row-tile, col-tile) cells: each cell streams a
[tile, s] slab of A and of B through VMEM (blocked BlockSpecs; the
Pallas pipeline double-buffers them) and writes one [tile, tile] output
block, while the full B operand rides separately in compiler-chosen
(HBM) space as the remote DMA's source. The copy START is pinned to the
FIRST grid cell and the semaphore WAIT to the LAST (`pl.when` on
`pl.program_id`; the DMA semaphores live in scratch, which persists
across the sequential grid), so the ICI transfer overlaps the whole
grid sweep — comm/compute overlap survives gridding, and ANY block size
streams. Tile rows are sized against the registered
``DREP_TPU_RING_VMEM_MB`` budget (:func:`fused_ring_tile`) — a sizing
knob, never a refusal.

Double buffering: each step's B receive buffer is a fresh `pallas_call`
output, and the host-stepped driver (parallel/allpairs.py) threads step
i's output in as step i+1's input — input buffer and output buffer
alternate roles every step, which IS the double-buffer swap; the DMA
always writes the buffer the receiver is NOT currently reading.

Rotation semantics are pinned to the existing ring's
``lax.ppermute(b, axis, [(j, (j+1) % D)])``: after the step, device m
holds what device m-1 held, so at step i device m computes block
``(m - i) mod D`` — the half-ring schedule, the host mirror, and the
per-block recovery indexing are all untouched. The merge-network tile
bodies are the SAME functions the ppermute ring jit-wraps
(ops/minhash.mash_tile_raw, ops/containment.containment_inter_tile_raw —
imported, not copied), so the produced block tiles are bit-identical;
tests pin this at D=3/8 in interpret mode, and the on-hardware
self-check re-proves it per process before the fast path is ever
selected.

MXU intersection-matmul variant (the ROADMAP's named escape hatch if
Mosaic rejects the in-kernel merge network at grid scale): for the
count-free |A∩B| tile (kind "containment" — packed ids are DENSE ranks,
ops/containment.pack_scaled_sketches) the tile can instead be computed
as a bf16 indicator matmul with the SAME DMA overlapped around it. Each
cell scatters its two id slabs into 0/1 VMEM indicator blocks — the
(hi, lo) = (id >> 7, id & 127) lane-decomposed scatter loop — one
vocab chunk at a time, and
accumulates `dot_general(ind_a, ind_b^T)` with
`preferred_element_type=f32` (ops/minhash_matmul.py's MXU idiom).
Indicators are exact 0/1, every count < 2^24: the f32 accumulation is
exact integer arithmetic, bit-identical to the merge-network tile's
int32→f32 cast. The variant is pinned with ``DREP_TPU_RING_VARIANT``
(default merge). Mash stays merge-only: its tile counts
shared ids within the bottom-s of the UNION (ops/minhash._pair_shared),
which is not a plain intersection matmul.

Why no neighbor barrier before the DMA: each `pallas_call` here performs
exactly ONE remote write into a buffer that XLA allocated before any
kernel in the step started, and the receive semaphore is hardware state
that tolerates signal-before-wait — the buffer-reuse races the
distributed guide's barriers guard against need a multi-round kernel,
which the host-stepped design deliberately avoids (the step boundary is
the checkpoint/redo unit from PR 4 and must stay host-visible).

NOT ON THE DEFAULT DISPATCH. On the one supported toolchain (jax/jaxlib
0.9.0, libtpu 0.0.34, TPU v5e) Mosaic refuses both tile bodies at
lowering: the merge variant traces the jnp tile (``jnp.flip`` ->
"Unimplemented primitive in Pallas TPU lowering for KernelType.TC: rev"),
the matmul variant reads scalars out of a loaded row (``row[c]`` ->
"Unimplemented primitive ...: dynamic_slice"). ``--ring_comm auto``
therefore resolves to the ``lax.ppermute`` ring
(parallel/allpairs.resolve_ring_comm). ``--ring_comm pallas_dma`` still
compiles this kernel on request — and raises what the compiler says; no
self-check decides at run time (ROADMAP D4 decides the kernel's fate).

Interpret mode (``interpret=True``) runs the SAME kernel — remote DMAs
discharged onto the shard axis as collectives — on any backend; it is
the CPU tier-1 equality oracle, never a performance claim.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from drep_tpu.parallel.mesh import AXIS

LANES = 128
# vocab chunk one matmul-variant cell scatters+multiplies at a time: two
# [tile, _MATMUL_V_CHUNK] int8 indicator blocks in VMEM scratch. Power of
# two so every pow2-bucketed v_pad divides evenly.
_MATMUL_V_CHUNK = 8192

# kinds whose tile is the plain count-free |A∩B| over dense-ranked ids —
# the only shape the indicator-matmul variant can express
MATMUL_TILE_KINDS = ("containment",)


def fused_ring_tile(
    n_local: int, sketch_width: int, n_outputs: int = 1,
    *, extra_row_bytes: int = 0, vmem_mb: int | None = None,
) -> int:
    """Rows per grid cell for a [n_local, sketch_width] int32 block pair:
    the largest halving of n_local whose estimated per-cell working set —
    pipeline-double-buffered A and B slabs (ids + counts) plus the
    [tile, tile] f32 output blocks plus any per-row scratch the variant
    adds — fits the ``DREP_TPU_RING_VMEM_MB`` budget. A sizing target for
    the Pallas pipeline, not a hard guarantee (tile-body temporaries are
    kernel-dependent); the knob exists so an operator can trade tile
    height for headroom without touching code. Never refuses: the floor
    is a single row."""
    from drep_tpu.utils import envknobs

    budget = (
        vmem_mb if vmem_mb is not None else envknobs.env_int("DREP_TPU_RING_VMEM_MB")
    ) << 20

    def working_set(t: int) -> int:
        slabs = 2 * (t * sketch_width * 4 + t * 4)  # A + B ids/counts
        tiles = n_outputs * t * t * 4
        return 2 * (slabs + tiles) + t * extra_row_bytes  # 2x: pipelining

    tile = max(1, int(n_local))
    while tile > 1 and working_set(tile) > budget:
        tile = (tile + 1) // 2
    return tile


def _raw_mash_tile(k: int):
    """The mash distance tile body WITHOUT the jit wrapper (pallas
    kernels trace their own program) — THE SAME tile body the ppermute
    ring's `mash_distance_tile` jit-wraps (ops/minhash.mash_tile_raw),
    so the two cannot drift; the unused jaccard output is dead-code-
    eliminated by the compiler."""
    from drep_tpu.ops.minhash import mash_tile_raw

    raw = mash_tile_raw(k)

    def tile(a_ids, a_counts, b_ids, b_counts):
        d, _j = raw(a_ids, a_counts, b_ids, b_counts)
        return d

    return tile


def _raw_containment_tile(k: int):
    """Symmetric |A∩B| tile body — THE SAME body `containment_inter_tile`
    jit-wraps (ops/containment.containment_inter_tile_raw), unjitted."""
    del k  # |A∩B| is count-free; k rides only in the cache key
    from drep_tpu.ops.containment import containment_inter_tile_raw

    def tile(a_ids, a_counts, b_ids, b_counts):
        del a_counts, b_counts
        return containment_inter_tile_raw(a_ids, b_ids)

    return tile


# kind -> (raw tile factory, n_outputs); mirrors allpairs._TILE_KINDS —
# every kind must keep tile(A,B) == tile(B,A).T bit-exact (the half-ring
# host mirror depends on it, same contract as the ppermute ring)
_RAW_TILE_KINDS = {
    "mash": (_raw_mash_tile, 1),
    "containment": (_raw_containment_tile, 1),
}


def _scatter_indicator_chunk(ids_ref, out_ref, base, v_chunk: int):
    """Scatter one vocab chunk [base, base+v_chunk) of sorted id rows into
    `out_ref` [rows, v_chunk/128, 128] int8 0/1 — a lane-decomposed
    VMEM scatter loop, restricted to the chunk. Rows are sorted ascending with a PAD_ID tail, so each row
    costs exactly its ids-in-chunk plus the skip scan; ids outside the
    chunk (including ragged-block padding garbage, which may be unsorted)
    are guarded out — a garbage row can only dirty its own output row,
    which the blocked out_spec masks on write-back anyway."""
    rows, w = ids_ref.shape
    out_ref[...] = jnp.zeros_like(out_ref)
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def row_body(r, _):
        # loaded once as a VALUE: while_loop conds must not read refs
        # (interpret-mode state discharge refuses ref effects in cond)
        row = ids_ref[r, :]
        c0 = lax.while_loop(
            lambda c: jnp.logical_and(c < w, row[c] < base),
            lambda c: c + 1,
            0,
        )

        def step(c):
            raw = row[c]
            ok = raw >= base
            idx = jnp.clip(raw - base, 0, v_chunk - 1)
            hi = idx // LANES
            lo = idx - hi * LANES
            cur = out_ref[r, pl.dslice(hi, 1), :]
            out_ref[r, pl.dslice(hi, 1), :] = jnp.where(
                jnp.logical_and(ok, lane == lo), 1, cur
            ).astype(jnp.int8)
            return c + 1

        lax.while_loop(
            lambda c: jnp.logical_and(c < w, row[c] < base + v_chunk),
            step,
            c0,
        )
        return 0

    lax.fori_loop(0, rows, row_body, 0)


def _matmul_intersection_tile(
    a_ids_ref, b_ids_ref, ind_a_ref, ind_b_ref, *, v_pad: int, v_chunk: int
):
    """[tile_a, tile_b] f32 |A∩B| via chunked bf16 indicator matmul —
    exact integer counts (< 2^24), bit-identical to the merge-network
    tile's int32→f32 cast. Vocab chunks are disjoint hash ranges, so the
    per-chunk products sum exactly (the ops/containment.py additivity
    contract)."""
    ta = a_ids_ref.shape[0]
    tb = b_ids_ref.shape[0]

    def chunk_body(c, acc):
        base = c * v_chunk
        _scatter_indicator_chunk(a_ids_ref, ind_a_ref, base, v_chunk)
        _scatter_indicator_chunk(b_ids_ref, ind_b_ref, base, v_chunk)
        a = ind_a_ref[...].reshape(ta, v_chunk).astype(jnp.bfloat16)
        b = ind_b_ref[...].reshape(tb, v_chunk).astype(jnp.bfloat16)
        return acc + lax.dot_general(
            a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )

    return lax.fori_loop(
        0, v_pad // v_chunk, chunk_body, jnp.zeros((ta, tb), jnp.float32)
    )


def _fused_step_kernel(
    a_ids_ref, a_counts_ref, b_ids_ref, b_counts_ref,
    b_ids_src_ref, b_counts_src_ref,
    *refs, tile_fn, n_outputs: int, n_devices: int, matmul_cfg,
):
    """One grid cell of the fused rotate+compare step. The first four
    refs are the cell's blocked VMEM slabs (A rows i, B rows j); the
    `_src` pair is the SAME full B operand in compiler-chosen space — the
    remote DMA's source. `refs` unpacks to (tile_refs..., b_ids_out_ref,
    b_counts_out_ref, 4 DMA semaphores, then the matmul variant's two
    indicator scratch blocks when active). Counts ride as [n, 1] (2-D
    keeps the DMA shape lane-friendly; the driver reshapes).

    The remote-copy START is pinned to the first grid cell and the WAIT
    to the last: the semaphores live in scratch, which Pallas carries
    across the sequential grid, so ONE full-operand ICI transfer
    overlaps the whole tile sweep."""
    tile_refs = refs[:n_outputs]
    b_ids_out_ref, b_counts_out_ref = refs[n_outputs : n_outputs + 2]
    ids_send, ids_recv, cts_send, cts_recv = refs[n_outputs + 2 : n_outputs + 6]
    ind_refs = refs[n_outputs + 6 :]

    i = pl.program_id(0)
    j = pl.program_id(1)
    ni = pl.num_programs(0)
    nj = pl.num_programs(1)
    my_id = lax.axis_index(AXIS)
    dst = lax.rem(my_id + 1, n_devices)  # == ppermute perm [(j, j+1) % D]
    copy_ids = pltpu.make_async_remote_copy(
        src_ref=b_ids_src_ref, dst_ref=b_ids_out_ref,
        send_sem=ids_send, recv_sem=ids_recv,
        device_id=dst, device_id_type=pltpu.DeviceIdType.MESH,
    )
    copy_cts = pltpu.make_async_remote_copy(
        src_ref=b_counts_src_ref, dst_ref=b_counts_out_ref,
        send_sem=cts_send, recv_sem=cts_recv,
        device_id=dst, device_id_type=pltpu.DeviceIdType.MESH,
    )

    # start the ICI transfer in the FIRST cell, then compute every tile
    # from the still-resident slabs — the DMA engine and the compute
    # units run concurrently across the whole grid sweep
    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _start():
        copy_ids.start()
        copy_cts.start()

    if matmul_cfg is not None:
        v_pad, v_chunk = matmul_cfg
        tile_refs[0][...] = _matmul_intersection_tile(
            a_ids_ref, b_ids_ref, ind_refs[0], ind_refs[1],
            v_pad=v_pad, v_chunk=v_chunk,
        )
    else:
        tiles = tile_fn(
            a_ids_ref[...], a_counts_ref[...][:, 0],
            b_ids_ref[...], b_counts_ref[...][:, 0],
        )
        if not isinstance(tiles, tuple):
            tiles = (tiles,)
        for ref, t in zip(tile_refs, tiles):
            # same f32 cast as the step program / standalone block recompute
            ref[...] = t.astype(jnp.float32)

    @pl.when(jnp.logical_and(i == ni - 1, j == nj - 1))
    def _wait():
        copy_ids.wait()
        copy_cts.wait()


@functools.lru_cache(maxsize=None)
def fused_ring_step_fn(
    kind: str, k: int, mesh, interpret: bool = False,
    variant: str = "merge", v_pad: int = 0, vmem_mb: int | None = None,
):
    """One jitted shard_map program per (kind, k, mesh, interpret,
    variant, v_pad): the gridded fused rotate+compare ring step. Call
    signature and output layout are IDENTICAL to
    allpairs._ring_step_fn(..., rotate=True) — the step-wise driver swaps
    one for the other per the resolved comm backend; the last
    (rotation-free) step always runs the plain program (nothing to
    overlap). `variant="matmul"` (MATMUL_TILE_KINDS only; `v_pad` = the
    pow2-bucketed dense-id extent, computed host-side by the driver)
    swaps the merge-network tile body for the MXU indicator matmul.
    Returns (fn, n_outputs)."""
    from jax.sharding import PartitionSpec as P

    if variant not in ("merge", "matmul"):
        raise ValueError(f"fused ring variant {variant!r}: expected merge|matmul")
    if variant == "matmul":
        if kind not in MATMUL_TILE_KINDS:
            raise ValueError(
                f"matmul ring variant supports {MATMUL_TILE_KINDS}, not {kind!r} "
                "(the mash tile counts union-bottom shared ids, not plain |A∩B|)"
            )
        if v_pad <= 0 or v_pad % LANES:
            raise ValueError(
                f"matmul ring variant needs a positive 128-multiple v_pad, got {v_pad}"
            )
    make_tile, n_outputs = _RAW_TILE_KINDS[kind]
    tile_fn = make_tile(k)
    D = mesh.devices.size
    v_chunk = min(v_pad, _MATMUL_V_CHUNK) if variant == "matmul" else 0

    def shard_body(a_ids, a_counts, b_ids, b_counts):
        n_local, s = a_ids.shape
        cts2 = a_counts.reshape(n_local, 1)
        b_cts2 = b_counts.reshape(n_local, 1)
        tile = fused_ring_tile(
            n_local, s, n_outputs,
            extra_row_bytes=2 * v_chunk if variant == "matmul" else 0,
            vmem_mb=vmem_mb,
        )
        n_r = -(-n_local // tile)
        scratch = [pltpu.SemaphoreType.DMA] * 4
        if variant == "matmul":
            scratch += [pltpu.VMEM((tile, v_chunk // LANES, LANES), jnp.int8)] * 2
        out = pl.pallas_call(
            functools.partial(
                _fused_step_kernel,
                tile_fn=tile_fn, n_outputs=n_outputs, n_devices=D,
                matmul_cfg=(v_pad, v_chunk) if variant == "matmul" else None,
            ),
            grid=(n_r, n_r),
            out_shape=(
                *[
                    jax.ShapeDtypeStruct((n_local, n_local), jnp.float32)
                    for _ in range(n_outputs)
                ],
                jax.ShapeDtypeStruct((n_local, s), b_ids.dtype),
                jax.ShapeDtypeStruct((n_local, 1), b_counts.dtype),
            ),
            # cell (i, j) streams A rows i and B rows j through VMEM
            # (ragged last blocks are padded on read / masked on write by
            # the blocked specs); the SAME b operand rides again in
            # compiler-chosen (HBM) space as the remote DMA's source, and
            # the receive buffers stay there too — they are the DMA's
            # destination, not compute operands this step
            in_specs=[
                pl.BlockSpec((tile, s), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((tile, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((tile, s), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((tile, 1), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=(
                *[
                    pl.BlockSpec(
                        (tile, tile), lambda i, j: (i, j), memory_space=pltpu.VMEM
                    )
                    for _ in range(n_outputs)
                ],
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ),
            scratch_shapes=scratch,
            interpret=interpret,
            compiler_params=pltpu.CompilerParams(collective_id=7),
        )(a_ids, cts2, b_ids, b_cts2, b_ids, b_cts2)
        *tiles, b_ids_next, b_cts_next = out
        return (*tiles, b_ids_next, b_cts_next.reshape(n_local))

    fn = jax.jit(
        jax.shard_map(
            shard_body,
            mesh=mesh,
            in_specs=(P(AXIS, None), P(AXIS), P(AXIS, None), P(AXIS)),
            out_specs=(
                *[P(AXIS, None) for _ in range(n_outputs)],
                P(AXIS, None),
                P(AXIS),
            ),
            # the kernel's remote DMA makes every output device-varying by
            # construction; the value-type checker cannot see through a
            # pallas_call (compiled or interpreted), so it is off here
            check_vma=False,
        )
    )
    return fn, n_outputs


def matmul_ring_vocab_pad(ids: np.ndarray) -> int:
    """The static v_pad the matmul variant needs, from the HOST copy of
    the packed id matrix (the driver holds it before sharding): pow2
    bucket of the dense-rank extent. Packed ids are ranks into the global
    vocabulary (ops/containment.pack_scaled_sketches), so the extent is
    max real id + 1 — PAD_ID (2^31-1) never scatters because every real
    extent is far below it."""
    from drep_tpu.ops.containment import _pow2_bucket
    from drep_tpu.ops.minhash import PAD_ID

    real = ids[ids != PAD_ID]
    extent = int(real.max()) + 1 if real.size else 1
    return _pow2_bucket(extent, LANES)


def fused_ring_variant(kind: str) -> str:
    """Which tile variant the fused step runs for `kind`: the env pin
    (``DREP_TPU_RING_VARIANT``) when set, else merge. Kinds outside
    MATMUL_TILE_KINDS are always merge — the matmul tile cannot express
    them."""
    from drep_tpu.utils import envknobs

    req = envknobs.env_str("DREP_TPU_RING_VARIANT") or "merge"
    if req not in ("merge", "matmul"):
        raise ValueError(f"DREP_TPU_RING_VARIANT={req!r}: expected merge|matmul")
    return req if kind in MATMUL_TILE_KINDS else "merge"

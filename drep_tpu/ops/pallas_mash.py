"""Pallas TPU kernel for the exact Mash union-bottom-s estimator.

The streaming primary stage (parallel/streaming.py — the 100k-genome path)
computes Mash distance tiles with the jnp bitonic merge
(ops/minhash.py::mash_distance_tile). That formulation materializes
[T, T, 2*S2] s32 temporaries in HBM and re-reads them once per merge
stage — measured HBM-bound at ~0.5 M pairs/s/chip on v5e. This kernel
keeps each [TILE, 2*S2] merge batch resident in VMEM: for each A row it
merges that row with every B row of the tile at once via Batcher's
bitonic merge (ops/merge.py is the jnp formulation; an ascending row
concatenated with a descending row is bitonic, so log2(2*S2)
compare-exchange stages of full-width `pltpu.roll` + min/max yield the
sorted merge, and adjacent duplicates are the intersection), then adds
the two pieces the Mash estimator needs beyond the intersection:

- a Hillis-Steele prefix sum over lanes (same roll+mask primitive as the
  merge stages) giving each merged position its DISTINCT rank in the
  union, and
- the per-pair cutoff s_use = min(|A|, |B|, s), so a duplicate only
  counts when its value lies within the bottom-s_use distinct hashes of
  the union — the proper Mash estimator, bit-identical to
  ops/minhash.py::_pair_shared (equality-tested in interpret mode by
  tests/test_pallas_mash.py; benchmark/ checks the compiled kernel's
  job against its plain reference on the chip).

Returns raw `shared` counts; the jaccard->distance transform runs on host
through the SAME mash_distance_from_jaccard the jnp path uses, so the two
paths cannot drift.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from drep_tpu.ops.merge import next_pow2
from drep_tpu.ops.minhash import PAD_ID, mash_distance_from_jaccard

TILE = 128  # both tile dims: the pair tile's last dim must be lane-width
# widest sketch whose [TILE, 2*S2] merge working set fits VMEM (~16 MB)
PALLAS_MAX_WIDTH = 2048


def _use_interpret() -> bool:
    # device platform, not jax.default_backend(): TPU access can ride a
    # plugin whose backend name differs while devices still report "tpu"
    return jax.devices()[0].platform != "tpu"


def _merge_bitonic(x: jnp.ndarray, length: int) -> jnp.ndarray:
    """Bitonic merge of a [..., length] bitonic batch along the last
    (lane) axis, via roll + masked min/max (Mosaic-friendly: no sub-lane
    reshapes)."""
    axis = x.ndim - 1
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    d = length // 2
    while d >= 1:
        left = pltpu.roll(x, length - d, axis)  # partner for the low half: x[p + d]
        right = pltpu.roll(x, d, axis)  # partner for the high half: x[p - d]
        low_half = (col % (2 * d)) < d
        x = jnp.where(low_half, jnp.minimum(x, left), jnp.maximum(x, right))
        d //= 2
    return x


def rows_per_iter(s2: int) -> int:
    """A-rows merged per kernel loop iteration (1, 2, or 4). >1 batches R
    broadcast-merge blocks into one [R, TB, 2*S2] VPU pass, amortizing the
    per-iteration fixed work (concat, loop bookkeeping) over R rows at R x
    the VMEM working set. Default 1 until a measurement on real hardware
    shows a win (the merge/prefix stages dominate and scale with elements,
    so the expected gain is the fixed-cost fraction only).

    Clamped so R * 2*S2 never exceeds 2 * (2*PALLAS_MAX_WIDTH) merged
    lanes per sublane block — the request that compiles at R=1/max width
    must not fail Mosaic allocation when the knob multiplies it."""
    from drep_tpu.utils import envknobs

    r = envknobs.env_int("DREP_TPU_MASH_ROWS_PER_ITER")
    if r not in (1, 2, 4):
        raise ValueError("DREP_TPU_MASH_ROWS_PER_ITER must be 1, 2, or 4")
    bound = max(1, (2 * PALLAS_MAX_WIDTH) // max(s2, 1))
    # power of two: the kernel loop runs TILE // r iterations, so r must
    # divide TILE or trailing rows would silently stay unwritten
    return min(r, 1 << (bound.bit_length() - 1))


def _prefix_sum_lanes(x: jnp.ndarray, length: int) -> jnp.ndarray:
    """Inclusive prefix sum along lanes via Hillis-Steele roll+mask stages
    (log2(length) passes, all VPU work on the VMEM-resident block)."""
    axis = x.ndim - 1
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    d = 1
    while d < length:
        shifted = pltpu.roll(x, d, axis)
        x = jnp.where(col >= d, x + shifted, x)
        d *= 2
    return x


def _shared_counts(x: jnp.ndarray, length: int, col: jnp.ndarray, s_use: jnp.ndarray) -> jnp.ndarray:
    """THE union-bottom-s estimator body, rank-agnostic (last axis = merged
    lanes): bitonic-merge the [..., length] bitonic batch, mark duplicates
    (== intersection), rank distinct union members, count duplicates whose
    rank is within the per-pair bottom-s cutoff. One definition shared by
    the r_iter==1 (2-D) and row-batched (3-D) kernel loops so the two can
    never drift."""
    axis = x.ndim - 1
    x = _merge_bitonic(x, length)
    is_real = x != PAD_ID
    prev = pltpu.roll(x, 1, axis)
    dup = (x == prev) & is_real & (col > 0)
    start = is_real & ~dup
    rank = _prefix_sum_lanes(start.astype(jnp.int32), length)
    counted = dup & (rank <= s_use)
    return jnp.sum(counted.astype(jnp.int32), axis=axis)


def _mash_shared_kernel(s_orig: int, r_iter: int, a_rev_ref, na_ref, b_ref, nb_ref, out_ref):
    """a_rev_ref [TA, S2] DESCENDING rows; b_ref [TB, S2] ascending rows;
    na_ref [TA, 1] / nb_ref [TB, 1] valid-entry counts; out_ref [TA, TB]
    int32 `shared` counts under the union-bottom-s rule. Processes
    `r_iter` A rows per loop iteration (see rows_per_iter)."""
    ta = a_rev_ref.shape[0]
    tb, s2 = b_ref.shape
    length = 2 * s2
    b_block = b_ref[:]
    nb_col = nb_ref[:]  # [TB, 1]

    if r_iter == 1:
        col = jax.lax.broadcasted_iota(jnp.int32, (tb, length), 1)

        def body(i, _):
            a_row = a_rev_ref[i, :]
            x = jnp.concatenate(
                [b_block, jnp.broadcast_to(a_row[None, :], (tb, s2))], axis=1
            )
            s_use = jnp.minimum(jnp.minimum(na_ref[i, 0], nb_col), s_orig)  # [TB, 1]
            out_ref[i, :] = _shared_counts(x, length, col, s_use)
            return 0

        jax.lax.fori_loop(0, ta, body, 0)
        return

    col3 = jax.lax.broadcasted_iota(jnp.int32, (r_iter, tb, length), 2)
    b3 = jnp.broadcast_to(b_block[None], (r_iter, tb, s2))

    def body_r(i, _):
        # Per-row dynamic loads/stores, not a [R, S2] block at offset
        # i*r_iter: Mosaic requires multi-row vector loads/stores to start
        # at a sublane multiple of 8, and i*{2,4} is not provably one.
        # Single-row
        # dynamic indexing is the supported pattern (it is what the
        # r_iter==1 path compiles to); the batched [R, TB, 2*S2] merge —
        # the point of the knob — is unchanged.
        base = i * r_iter
        a_rows = jnp.concatenate(
            [a_rev_ref[base + t, :][None, :] for t in range(r_iter)], axis=0
        )  # [R, S2]
        x = jnp.concatenate(
            [b3, jnp.broadcast_to(a_rows[:, None, :], (r_iter, tb, s2))], axis=2
        )
        na_rows = jnp.concatenate(
            [na_ref[base + t, :][None, :] for t in range(r_iter)], axis=0
        )  # [R, 1]
        s_use = jnp.minimum(
            jnp.minimum(na_rows[:, :, None], nb_col[None]), s_orig
        )  # [R, TB, 1]
        res = _shared_counts(x, length, col3, s_use)  # [R, TB]
        for t in range(r_iter):
            out_ref[base + t, :] = res[t, :]
        return 0

    jax.lax.fori_loop(0, ta // r_iter, body_r, 0)


@functools.partial(jax.jit, static_argnames=("s_orig", "r_iter", "interpret"))
def _mash_shared_grid(a_rev, na, b, nb, *, s_orig: int, r_iter: int, interpret: bool):
    ta_n, s2 = a_rev.shape
    tb_n = b.shape[0]
    grid = (ta_n // TILE, tb_n // TILE)
    return pl.pallas_call(
        functools.partial(_mash_shared_kernel, s_orig, r_iter),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE, s2), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE, 1), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE, s2), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE, 1), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (TILE, TILE), lambda i, j: (i, j), memory_space=pltpu.VMEM
        ),
        # vma: inside a shard_map (the mesh ring's tile) the result varies
        # over whatever axes the operands vary over; empty outside one
        out_shape=jax.ShapeDtypeStruct(
            (ta_n, tb_n), jnp.int32,
            vma=jax.typeof(a_rev).vma | jax.typeof(b).vma,
        ),
        interpret=interpret,
    )(a_rev, na, b, nb)


@functools.partial(jax.jit, static_argnames=("s_orig", "r_iter", "interpret"))
def _mash_shared_grid_symmetric(a_rev, na, b, nb, *, s_orig: int, r_iter: int, interpret: bool):
    """Self-comparison: shared counts are symmetric in (A, B), so the
    (T, T//2+1) wrapped grid — cell (i, jj) computes tile (i, (i+jj)%T) —
    covers every unordered tile pair at ~2x less kernel work (for even T
    the last column double-covers half, the assemble reads one copy).
    Output is the compact wrapped matrix [n, (T//2+1)*TILE];
    :func:`_assemble_symmetric` turns it into distances on host."""
    n, s2 = a_rev.shape
    t = n // TILE
    th = t // 2 + 1
    grid = (t, th)
    return pl.pallas_call(
        functools.partial(_mash_shared_kernel, s_orig, r_iter),
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE, s2), lambda i, jj: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((TILE, 1), lambda i, jj: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (TILE, s2), lambda i, jj: ((i + jj) % t, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (TILE, 1), lambda i, jj: ((i + jj) % t, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (TILE, TILE), lambda i, jj: (i, jj), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((n, th * TILE), jnp.int32),
        interpret=interpret,
    )(a_rev, na, b, nb)


def _assemble_symmetric(
    compact: np.ndarray,
    counts: np.ndarray,
    s_orig: int,
    k: int,
    tile: int,
    jaccard: bool = False,
) -> tuple[np.ndarray, np.ndarray | None, int, int]:
    """[rows, th*tile] wrapped-compact shared counts -> the symmetric
    [n, n] float32 distances (n = len(counts), rows = n padded to a
    multiple of `tile`), a tile at a time: wrapped cell (i, jj) holds tile
    (i, (i+jj)%t), goes through THE transform on its own and lands in
    `dist` with its transpose. A whole-matrix transform first-touches some
    twenty n^2 temporaries, each from a fresh mmap (2.4 s at n = 5,000 on
    the chip host); a tile's are allocator-recycled and stay in cache, and
    the values are the same bit for bit (an elementwise formula). For even
    t the last wrapped column holds each of its tile pairs twice: the
    first copy (i < t/2) is skipped, so every cell is written once. The
    Jaccard matrix only for a caller that reads it. Returns (dist, jaccard
    or None, tiles transformed, cells written); the diagonals are the
    caller's."""
    n = len(counts)
    t = compact.shape[0] // tile
    th = compact.shape[1] // tile
    dist = np.empty((n, n), np.float32)
    jac = np.empty((n, n), np.float32) if jaccard else None
    tiles = cells = 0
    for i in range(t):
        rows = slice(i * tile, min((i + 1) * tile, n))
        for jj in range(th):
            if 2 * jj == t and 2 * i < t:
                continue  # tile (i+jj, i) of the same column is its twin
            j = (i + jj) % t
            cols = slice(j * tile, min((j + 1) * tile, n))
            blk = compact[rows, jj * tile : jj * tile + (cols.stop - cols.start)]
            d, jb = shared_counts_to_distance(blk, counts[rows], counts[cols], s_orig, k)
            for out, val in ((dist, d), (jac, jb)):
                if out is not None:
                    out[rows, cols] = val
                    if i != j:
                        out[cols, rows] = val.T
            tiles += 1
            cells += blk.size * (1 if i == j else 2)
    return dist, jac, tiles, cells


def all_vs_all_mash_pallas(
    packed, k: int = 21, jaccard: bool = False
) -> tuple[np.ndarray, np.ndarray | None]:
    """Full [N, N] (distance, jaccard) for one packed sketch set — the
    single-chip TPU primary engine: the reference-faithful union-bottom-s
    estimator. Same output contract as ops/minhash.py::all_vs_all_mash,
    but the Jaccard matrix is None unless `jaccard` asks for it (the one
    production caller, engines.mash_distance_matrix, drops it)."""
    from drep_tpu.utils.profiling import counters

    n = packed.n
    ids, counts = packed.ids, packed.counts
    width = ids.shape[1]
    s2 = max(128, next_pow2(width))
    rows = -(-n // TILE) * TILE
    # wrapped symmetric grid: t*(t//2+1) tiles of the t^2 full grid (for
    # even t the last wrapped column double-covers half its tiles, so the
    # count sits slightly above the exact triangle — recorded as executed)
    t_blocks = rows // TILE
    counters.add_tiles(
        "primary_compare",
        computed=t_blocks * (t_blocks // 2 + 1),
        total=t_blocks * t_blocks,
    )
    with counters.span("primary/pack"):
        a = np.full((rows, s2), PAD_ID, np.int32)
        a[:n, :width] = ids
        cc = np.zeros((rows, 1), np.int32)
        cc[:n, 0] = counts
        a_rev = np.ascontiguousarray(a[:, ::-1])
    # the call ships its four host operands and enqueues the one grid
    with counters.span("primary/dispatch", rows=rows):
        pending = _mash_shared_grid_symmetric(
            a_rev, cc, a, cc,
            s_orig=width, r_iter=rows_per_iter(s2), interpret=_use_interpret(),
        )
    with counters.span("primary/wait", rows=rows):
        compact = np.asarray(pending)
    with counters.span("primary/assemble") as span:
        dist, jac, tiles, cells = _assemble_symmetric(
            compact, counts, width, k, TILE, jaccard=jaccard
        )
        span.note(tiles=tiles, cells=cells)
        np.fill_diagonal(dist, 0.0)
        if jac is not None:
            np.fill_diagonal(jac, 1.0)
    return dist, jac


def shared_counts_to_distance(
    shared: np.ndarray,
    a_counts: np.ndarray,
    b_counts: np.ndarray,
    s_orig: int,
    k: int,
    xp=np,
) -> tuple[np.ndarray, np.ndarray]:
    """(distance, jaccard) float32 from raw `shared` counts — THE single
    transform for every Pallas-mash consumer (full matrix, tile wrapper,
    streaming — host via xp=np, on-device inside the streaming compact
    jit via xp=jnp), so the estimator cannot drift between them.
    All-float32 intermediates: an int64 outer + float64 division would
    triple transient memory at large N for no precision gain (counts are
    bounded by the sketch width)."""
    s_use = xp.minimum(
        xp.minimum(
            a_counts.astype(xp.int32)[:, None], b_counts.astype(xp.int32)[None, :]
        ),
        xp.int32(s_orig),
    ).astype(xp.float32)
    j = xp.where(
        s_use > 0, shared.astype(xp.float32) / xp.maximum(s_use, xp.float32(1.0)), xp.float32(0.0)
    ).astype(xp.float32)
    dist = mash_distance_from_jaccard(j, k, xp=xp).astype(xp.float32)
    return dist, j


def pallas_mash_supported(sketch_width: int) -> bool:
    """True when the compiled kernel path applies: on-TPU and the padded
    width fits the VMEM budget. A wider sketch on a TPU (`-ms` over
    PALLAS_MAX_WIDTH; no deployment sketches wider than 1,000) takes the
    jnp sort tiles (ops/minhash.all_vs_all_mash) at any N: the same
    estimator, its merge temporaries in HBM."""
    return (
        not _use_interpret()
        and max(128, next_pow2(sketch_width)) <= PALLAS_MAX_WIDTH
    )


def mash_distance_tile_device(a_ids, a_counts, b_ids, b_counts, *, k: int = 21):
    """Traceable twin of :func:`mash_distance_tile_pallas`: [Ta, Tb] float32
    Mash distances from device operands, every step on the device — rows
    padded to TILE multiples, widths to the kernel's power of two, the A
    side reversed, the raw shared counts turned into distances by THE
    shared transform. For callers that are themselves traced (the mesh
    ring's per-block tile, parallel/allpairs.py): the kernel keeps each
    merge in VMEM, where the jnp tile (ops/minhash.mash_distance_tile)
    materializes [Ta, Tb, 2*S2] temporaries in HBM."""
    ta, s_orig = a_ids.shape
    tb = b_ids.shape[0]
    s2 = max(128, next_pow2(s_orig))

    def _pad(ids, counts):
        rows = -(-ids.shape[0] // TILE) * TILE
        ids = jnp.pad(
            ids, ((0, rows - ids.shape[0]), (0, s2 - s_orig)), constant_values=PAD_ID
        )
        return ids, jnp.pad(counts, (0, rows - counts.shape[0]))[:, None]

    a, na_col = _pad(a_ids, a_counts)
    b, nb_col = _pad(b_ids, b_counts)
    shared = _mash_shared_grid(
        jnp.flip(a, axis=1), na_col, b, nb_col,
        s_orig=s_orig, r_iter=rows_per_iter(s2), interpret=_use_interpret(),
    )[:ta, :tb]
    dist, _j = shared_counts_to_distance(shared, a_counts, b_counts, s_orig, k, xp=jnp)
    return dist


def mash_distance_tile_pallas(a_ids, a_counts, b_ids, b_counts, *, k: int = 21):
    """Drop-in for ops/minhash.py::mash_distance_tile (distance only):
    [Ta, Tb] float32 Mash distances between two packed sketch blocks.

    Accepts numpy or device arrays; rows are padded to TILE multiples and
    widths to a shared power of two on host. Trimming happens here, so
    callers see exactly the [Ta, Tb] they asked for.
    """
    a_ids = np.asarray(a_ids)
    b_ids = np.asarray(b_ids)
    a_counts = np.asarray(a_counts)
    b_counts = np.asarray(b_counts)
    na, nb = a_ids.shape[0], b_ids.shape[0]
    s_orig = max(a_ids.shape[1], b_ids.shape[1])
    s2 = max(128, next_pow2(s_orig))

    def _pad(ids, counts):
        rows = -(-ids.shape[0] // TILE) * TILE
        out = np.full((rows, s2), PAD_ID, dtype=np.int32)
        out[: ids.shape[0], : ids.shape[1]] = ids
        cnt = np.zeros((rows, 1), dtype=np.int32)
        cnt[: counts.shape[0], 0] = counts
        return out, cnt

    a, na_col = _pad(a_ids, a_counts)
    b, nb_col = _pad(b_ids, b_counts)
    shared = np.asarray(
        _mash_shared_grid(
            np.ascontiguousarray(a[:, ::-1]), na_col, b, nb_col,
            s_orig=s_orig, r_iter=rows_per_iter(s2), interpret=_use_interpret(),
        )
    )[:na, :nb]
    return shared_counts_to_distance(shared, a_counts, b_counts, s_orig, k)

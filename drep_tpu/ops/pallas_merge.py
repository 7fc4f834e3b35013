"""Pallas TPU kernel: batched k-mer containment via in-VMEM bitonic merge.

The hot op of the `jax_ani` secondary stage (SURVEY.md §7 step 6 calls for
exactly this kernel) is the pairwise intersection size of sorted hash-id
rows — the TPU-native replacement for fastANI's k-mer containment core
(drep/d_cluster/external.py::run_pairwise_fastANI upstream; reference mount
empty). The production MXU indicator-matmul path (ops/containment.py) is
preferred while the [m, vocab] indicator fits its budget; THIS kernel is
the scale path: its cost is O(S log S) per pair regardless of vocabulary
size, so giant primary clusters (where vocab * m blows the matmul budget)
stay fast without falling back to scalar-unit gathers.

Per grid cell (one [TA, 128] tile of the pair matrix) the kernel keeps one
A block and one B block resident in VMEM and, for each A row, merges it
with every B row at once via Batcher's bitonic merge (ops/merge.py is the
jnp formulation): an ascending row concatenated with a descending row is
bitonic, so log2(2S) compare-exchange stages — implemented as full-width
`pltpu.roll` + min/max, all VPU work with no lane-hostile reshapes — yield
the sorted merge, and adjacent duplicates are exactly the intersection.

TPU block constraints pin the pair-tile's last dim to 128 (the lane width),
so the B tile is fixed at 128 rows and VMEM budget caps the mergeable
sketch width (PALLAS_MAX_WIDTH). Wider sketches — the PRODUCTION regime:
4 Mb genomes at default scale=200 are ~20k-wide — are range-partitioned
(ops/rangepart.py): intersection counts are additive over disjoint hash
ranges, so each bucket repacks to <= PALLAS_MAX_WIDTH, runs this same
VMEM-resident kernel, and the counts sum. Total merge work SHRINKS
(R buckets of S/R cost S*log(2S/R) < S*log(2S)), and nothing ever exceeds
the VMEM working set. The jnp formulation of the merge remains as the
non-TPU fallback, with its HBM temporaries capped by the shared budget
rule (ops/merge.py::cap_merge_tile — an uncapped 128-tile at width 32768
would materialize ~4.3 GB per temp).

CPU/test execution uses `interpret=True` (the reference has no fake
backend; we follow SURVEY.md §4's rebuild note instead).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from drep_tpu.ops.merge import merge_sorted_rows, next_pow2
from drep_tpu.ops.minhash import (
    PAD_ID,
    PackedSketches,
    pad_sentinel,
    require_int32_ids,
    widen_ids_device,
)

TILE_B = 128  # lane width — the pair tile's last dim must be 128-aligned
TILE_A = 128
# widest sketch whose [TILE_B, 2*S2] merge working set fits VMEM (~16 MB)
PALLAS_MAX_WIDTH = 2048


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


def _intersect_kernel(a_ref, b_ref, out_ref):
    """a_ref [TA, S2] DESCENDING rows; b_ref [TB, S2] ascending rows;
    out_ref [TA, TB] int32 pairwise intersection counts."""
    ta = a_ref.shape[0]
    tb, s2 = b_ref.shape
    length = 2 * s2
    b_block = b_ref[:]
    col = jax.lax.broadcasted_iota(jnp.int32, (tb, length), 1)

    def body(i, _):
        a_row = a_ref[i, :]
        x = jnp.concatenate(
            [b_block, jnp.broadcast_to(a_row[None, :], (tb, s2))], axis=1
        )
        x = _merge_bitonic(x, length)
        prev = pltpu.roll(x, 1, 1)
        dup = (x == prev) & (x != PAD_ID) & (col > 0)
        out_ref[i, :] = jnp.sum(dup.astype(jnp.int32), axis=1)
        return 0

    jax.lax.fori_loop(0, ta, body, 0)


def _intersect_kernel_stacked(a_ref, b_ref, out_ref):
    """Fused range-bucket variant of :func:`_intersect_kernel`.

    a_ref [1, TA, S2] DESCENDING rows of ONE range bucket; b_ref
    [1, TB, S2] ascending rows of the same bucket; out_ref [TA, TB] int32
    counts ACCUMULATED across the innermost grid dimension (buckets):
    intersection counts are additive over disjoint id ranges, and the out
    index_map ignores the bucket index, so consecutive grid steps revisit
    the same output tile — zeroed at bucket 0, added to after (the
    standard Mosaic reduction-dimension pattern, cf. a matmul K loop).
    One launch + one stacked operand transfer replaces R separate
    launches/transfers (overhead-bound, not compute-bound, in an earlier
    chip run; not re-measured)."""
    ta = a_ref.shape[1]
    tb, s2 = b_ref.shape[1], b_ref.shape[2]
    length = 2 * s2
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    b_block = b_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (tb, length), 1)

    def body(i, _):
        a_row = a_ref[0, i, :]
        x = jnp.concatenate(
            [b_block, jnp.broadcast_to(a_row[None, :], (tb, s2))], axis=1
        )
        x = _merge_bitonic(x, length)
        prev = pltpu.roll(x, 1, 1)
        dup = (x == prev) & (x != PAD_ID) & (col > 0)
        out_ref[i, :] = out_ref[i, :] + jnp.sum(dup.astype(jnp.int32), axis=1)
        return 0

    jax.lax.fori_loop(0, ta, body, 0)


# uint16 stacked buckets (per-bucket rebased, U16_PAD sentinel — the
# half-link-bytes plan from rangepart.stacked_range_buckets) widen to the
# kernel's int32/PAD_ID contract ON DEVICE via minhash.widen_ids_device,
# after the one cheap transfer
_widen_ids = widen_ids_device


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _intersect_grid_symmetric_stacked(stacked, *, tile: int, interpret: bool):
    """Self-comparison over stacked range buckets [R, na, S2] (ascending
    rows): the wrapped symmetric half-grid of `_intersect_grid_symmetric`
    with an innermost bucket dimension accumulating into each output tile.
    The A-side reversal happens ON DEVICE (jnp.flip) so the host ships the
    stacked tensor once, not twice."""
    stacked = _widen_ids(stacked)
    r_n, na, s2 = stacked.shape
    a_rev = jnp.flip(stacked, axis=2)
    t = na // tile
    th = t // 2 + 1
    grid = (t, th, r_n)
    return pl.pallas_call(
        _intersect_kernel_stacked,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, tile, s2), lambda i, jj, r: (r, i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, tile, s2),
                lambda i, jj, r: (r, (i + jj) % t, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (tile, tile), lambda i, jj, r: (i, jj), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((na, th * tile), jnp.int32),
        interpret=interpret,
    )(a_rev, stacked)


@functools.partial(jax.jit, static_argnames=("tile_a", "tile_b", "interpret"))
def _intersect_grid_rect_stacked(a_stacked, b_stacked, *, tile_a: int, tile_b: int, interpret: bool):
    """Rectangular stacked-bucket grid: [R, na, S2] x [R, nb, S2] ->
    [na, nb] accumulated across the innermost bucket dimension."""
    a_stacked = _widen_ids(a_stacked)
    b_stacked = _widen_ids(b_stacked)
    r_n, na, s2 = a_stacked.shape
    nb = b_stacked.shape[1]
    a_rev = jnp.flip(a_stacked, axis=2)
    grid = (na // tile_a, nb // tile_b, r_n)
    return pl.pallas_call(
        _intersect_kernel_stacked,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, tile_a, s2), lambda i, j, r: (r, i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, tile_b, s2), lambda i, j, r: (r, j, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (tile_a, tile_b), lambda i, j, r: (i, j), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((na, nb), jnp.int32),
        interpret=interpret,
    )(a_rev, b_stacked)


def _pad_rows_stacked(stacked: np.ndarray, multiple: int) -> np.ndarray:
    """Pad the row axis (axis=1) of a [R, N, W] stacked tensor to a tile
    multiple with the dtype's pad sentinel."""
    n = stacked.shape[1]
    nt = -(-n // multiple) * multiple
    if nt == n:
        return stacked
    return np.pad(
        stacked, ((0, 0), (0, nt - n), (0, 0)),
        constant_values=pad_sentinel(stacked.dtype),
    )


def _use_interpret() -> bool:
    # device platform, not jax.default_backend(): TPU access can ride a
    # plugin whose backend name differs while devices still report "tpu"
    return jax.devices()[0].platform != "tpu"


@functools.partial(jax.jit, static_argnames=("tile_a", "tile_b", "interpret"))
def _intersect_grid(a_rev, b, *, tile_a: int, tile_b: int, interpret: bool):
    na, s2 = a_rev.shape
    nb = b.shape[0]
    grid = (na // tile_a, nb // tile_b)
    return pl.pallas_call(
        _intersect_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_a, s2), lambda i, j: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile_b, s2), lambda i, j: (j, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (tile_a, tile_b), lambda i, j: (i, j), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((na, nb), jnp.int32),
        interpret=interpret,
    )(a_rev, b)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _intersect_grid_symmetric(a_rev, b, *, tile: int, interpret: bool):
    """Self-comparison grid: intersections are symmetric, so instead of the
    full T x T tile grid, a (T, T//2 + 1) wrapped grid — cell (i, jj)
    computes tile (i, (i+jj) % T) — covers every unordered tile pair
    (~2x less kernel work; for even T the last column double-covers half,
    the unwrap just overwrites). Output is the compact wrapped matrix
    [na, (T//2+1)*tile]; `_unwrap_symmetric` scatters it on host."""
    na, s2 = a_rev.shape
    t = na // tile
    th = t // 2 + 1
    grid = (t, th)
    return pl.pallas_call(
        _intersect_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile, s2), lambda i, jj: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (tile, s2), lambda i, jj: ((i + jj) % t, 0), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (tile, tile), lambda i, jj: (i, jj), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((na, th * tile), jnp.int32),
        interpret=interpret,
    )(a_rev, b)


def _unwrap_symmetric(compact: np.ndarray, tile: int) -> np.ndarray:
    """[na, th*tile] wrapped-compact tiles -> full symmetric [na, na]."""
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


@functools.partial(jax.jit, static_argnames=())
def _intersect_tile_jnp(a_ids, b_ids):
    """jnp fallback: same merge, vmapped over a pair tile; XLA manages the
    temporaries, so any sketch width works (at HBM-spill cost)."""

    def one_pair(a, b):
        x = merge_sorted_rows(a, b)
        dup = (x[1:] == x[:-1]) & (x[1:] != PAD_ID)
        return jnp.sum(dup.astype(jnp.int32))

    row = jax.vmap(one_pair, in_axes=(None, 0))
    return jax.vmap(row, in_axes=(0, None))(a_ids, b_ids)


def _pad_cols_pow2(ids: np.ndarray, s2: int) -> np.ndarray:
    if ids.shape[1] == s2:
        return ids
    out = np.full((ids.shape[0], s2), PAD_ID, dtype=ids.dtype)
    out[:, : ids.shape[1]] = ids
    return out


def _pad_rows(ids: np.ndarray, multiple: int) -> np.ndarray:
    n = ids.shape[0]
    nt = -(-n // multiple) * multiple
    if nt == n:
        return ids
    return np.pad(ids, ((0, nt - n), (0, 0)), constant_values=PAD_ID)


def _intersect_jnp_tiled(a: np.ndarray, b: np.ndarray, jnp_tile: int) -> np.ndarray:
    """Capped host-tiled jnp merge — the non-TPU over-width fallback. The
    tile obeys the shared sort-merge HBM budget (cap_merge_tile), never the
    raw request: an uncapped tile at production widths OOMs the chip."""
    from drep_tpu.ops.merge import cap_merge_tile

    tile = cap_merge_tile(jnp_tile, a.shape[1])
    a = _pad_rows(a, tile)
    b = _pad_rows(b, tile)
    inter = np.zeros((a.shape[0], b.shape[0]), dtype=np.int32)
    for i0 in range(0, a.shape[0], tile):
        for j0 in range(0, b.shape[0], tile):
            inter[i0 : i0 + tile, j0 : j0 + tile] = np.asarray(
                _intersect_tile_jnp(a[i0 : i0 + tile], b[j0 : j0 + tile])
            )
    return inter


def intersect_counts_pallas(
    a_ids: np.ndarray,
    b_ids: np.ndarray,
    jnp_tile: int = 128,
    force: str | None = None,
) -> np.ndarray:
    """Pairwise |A_i ∩ B_j| for sorted PAD_ID-padded int32 id rows.

    Returns int32 [na, nb]. Rows are padded to tile multiples and widths to
    a shared power of two on the host; the Pallas kernel is fixed-shape.
    Widths beyond PALLAS_MAX_WIDTH range-partition into narrow buckets and
    re-enter the kernel (counts are additive over disjoint hash ranges); on
    non-TPU backends they stream through the budget-capped jnp merge
    instead (range-bucketing under interpret=True would run the kernel in
    Python per grid cell). `force` ('range' | 'jnp') pins the path so tests
    exercise both on CPU.
    """
    require_int32_ids(a_ids, "intersect_counts_pallas")
    require_int32_ids(b_ids, "intersect_counts_pallas")
    na, nb = a_ids.shape[0], b_ids.shape[0]
    s2 = max(128, next_pow2(max(a_ids.shape[1], b_ids.shape[1])))
    a = _pad_cols_pow2(np.ascontiguousarray(a_ids), s2)
    b = _pad_cols_pow2(np.ascontiguousarray(b_ids), s2)

    if s2 <= PALLAS_MAX_WIDTH:
        a = _pad_rows(a, TILE_A)
        b = _pad_rows(b, TILE_B)
        # reverse A rows host-side: ascending ++ reversed-ascending = bitonic
        inter = _intersect_grid(
            np.ascontiguousarray(a[:, ::-1]),
            b,
            tile_a=TILE_A,
            tile_b=TILE_B,
            interpret=_use_interpret(),
        )
        return np.asarray(inter)[:na, :nb]

    if force == "range" or (force is None and not _use_interpret()):
        from drep_tpu.ops.rangepart import stacked_range_buckets

        # ONE stacked [R, n, W] tensor per side, one transfer, one fused
        # launch with bucket accumulation inside the grid (per-bucket
        # repack/transfer/launch loops were overhead-bound)
        a_st, b_st = stacked_range_buckets([a, b], PALLAS_MAX_WIDTH)
        if a_st.shape[0] == 0:
            return np.zeros((na, nb), dtype=np.int32)
        inter = _intersect_grid_rect_stacked(
            _pad_rows_stacked(a_st, TILE_A),
            _pad_rows_stacked(b_st, TILE_B),
            tile_a=TILE_A,
            tile_b=TILE_B,
            interpret=_use_interpret(),
        )
        return np.asarray(inter)[:na, :nb]

    return _intersect_jnp_tiled(a, b, jnp_tile)[:na, :nb]


def _count_self_tiles(n_rows: int, tile: int, half_grid: bool) -> None:
    """Record the self-comparison schedule that ACTUALLY ran into the
    secondary tile counters: the wrapped half-grid's t*(t//2+1) tiles, or
    the full t^2 when a fallback took the rectangular walk — the counter
    exists to expose full-grid regressions, so it must never claim the
    triangular schedule for a path that did not run it."""
    from drep_tpu.utils.profiling import counters

    t = -(-n_rows // tile)
    counters.add_tiles(
        "secondary_compare",
        computed=t * (t // 2 + 1) if half_grid else t * t,
        total=t * t,
    )


def intersect_counts_pallas_self(
    ids: np.ndarray, jnp_tile: int = 128, force: str | None = None
) -> np.ndarray:
    """|A_i ∩ A_j| for all pairs within one sketch set. Symmetric, so the
    Pallas path runs the wrapped half-grid (~2x less work than the general
    rectangular call); over-width sets range-partition and re-enter the
    half-grid per bucket (same row order every bucket, so symmetry holds)."""
    require_int32_ids(ids, "intersect_counts_pallas_self")
    n = ids.shape[0]
    s2 = max(128, next_pow2(ids.shape[1]))
    a = _pad_cols_pow2(np.ascontiguousarray(ids), s2)
    if s2 > PALLAS_MAX_WIDTH:
        if force == "range" or (force is None and not _use_interpret()):
            from drep_tpu.ops.rangepart import stacked_range_buckets

            # ONE stacked [R, n, W] tensor, one transfer, one fused launch:
            # the wrapped half-grid gains an innermost bucket dimension
            # that accumulates into each output tile (see
            # _intersect_kernel_stacked) — replacing the per-bucket
            # repack/transfer/launch loop that measured overhead-bound
            (stacked,) = stacked_range_buckets([a], PALLAS_MAX_WIDTH)
            if stacked.shape[0] == 0:
                return np.zeros((n, n), dtype=np.int32)
            _count_self_tiles(n, TILE_A, half_grid=True)
            compact = _intersect_grid_symmetric_stacked(
                _pad_rows_stacked(stacked, TILE_A),
                tile=TILE_A,
                interpret=_use_interpret(),
            )
            return _unwrap_symmetric(np.asarray(compact), TILE_A)[:n, :n]
        from drep_tpu.ops.merge import cap_merge_tile

        _count_self_tiles(n, cap_merge_tile(jnp_tile, a.shape[1]), half_grid=False)
        return _intersect_jnp_tiled(a, a, jnp_tile)[:n, :n]
    a = _pad_rows(a, TILE_A)
    _count_self_tiles(n, TILE_A, half_grid=True)
    compact = _intersect_grid_symmetric(
        np.ascontiguousarray(a[:, ::-1]),
        a,
        tile=TILE_A,
        interpret=_use_interpret(),
    )
    return _unwrap_symmetric(np.asarray(compact), TILE_A)[:n, :n]


def all_vs_all_containment_pallas(
    packed: PackedSketches, k: int = 21
) -> tuple[np.ndarray, np.ndarray]:
    """([N,N] symmetric max-containment ani, [N,N] directional cov) via
    the merge kernel — same contract as ops/containment.py's other
    all_vs_all_* paths: cov[i,j] = |A_i ∩ A_j| / |A_i|, ani =
    max(cov, cov.T)^(1/k), diagonals pinned to 1."""
    from drep_tpu.ops.containment import ani_cov_from_intersections

    # tile accounting happens inside intersect_counts_pallas_self, per the
    # schedule branch that actually runs (half-grid vs jnp full fallback)
    inter = intersect_counts_pallas_self(packed.ids)
    return ani_cov_from_intersections(inter, packed.counts, k)

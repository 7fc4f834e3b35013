"""MXU path for the all-vs-all MinHash Jaccard — chunked indicator matmuls.

Motivation: the sort-based estimator (ops/minhash.py) is VPU-bound at
O(s log^2 s) per pair. Intersection counts, however, are a matmul:
``inter[i,j] = <ind_i, ind_j>`` over the hash-id vocabulary, which puts the
whole primary stage on the systolic array (measured ~10-20x faster at
production shapes on v5e).

Estimator (common-threshold MinHash, exact — not an approximation of
Jaccard): for pair (i, j) let t = min(t_i, t_j) where t_i is the largest
hash in sketch i (its bottom-s threshold). Below t, BOTH sketches are
complete samples of their genomes, so

    j_est = |S_i ∩ S_j| / (|S_i <= t| + |S_j <= t| - |S_i ∩ S_j|)

is an unbiased Jaccard estimate with effective sample size ~s (every
element of the intersection is automatically <= t). This differs from the
reference Mash's union-bottom-s estimator only in which unbiased sample it
conditions on (per-pair values differ within estimator variance; both are
validated against oracles in tests).

Execution: hash ids are globally column-sorted and cut into chunks at
column boundaries; within a chunk, columns are relabeled dense (any
injective relabeling preserves inner products), so every chunk scatters
into the same fixed [N, W] indicator and one ``lax.scan`` accumulates

    inter += I @ I.T          (intersection counts, MXU)

The below-threshold counts ``below[i,j] = |S_i <= t_j|`` need NO matmul:
rows are already sorted, so one host `searchsorted` per row produces them
exactly — and it runs WHILE the device chews the async-dispatched
intersection scan, so it costs ~zero wall-clock (measured ~2.9x faster
than the original two-matmul formulation on v5e at N=2048).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from drep_tpu.ops.minhash import (
    PAD_ID,
    PackedSketches,
    mash_distance_from_jaccard,
    pad_packed_rows,
)

# per-chunk entry budget: W columns of bf16 indicator [N, W]. Chosen so the
# indicator stays ~tens of MB for a few thousand rows.
DEFAULT_CHUNK_ENTRIES = 16384


def _build_chunks(ids: np.ndarray, chunk_entries: int):
    """Column-sorted (row, dense-col) chunk tensors, padded to a common
    width; chunks never split a column (inner products need every
    occurrence of a hash id in the same chunk)."""
    n, s = ids.shape
    valid = ids != PAD_ID
    rows_flat = np.repeat(np.arange(n, dtype=np.int32), s)[valid.ravel()]
    cols_flat = ids.ravel()[valid.ravel()]
    order = np.argsort(cols_flat, kind="stable")
    rows_flat = rows_flat[order]
    cols_flat = cols_flat[order]
    total = len(cols_flat)

    cuts = [0]
    while cuts[-1] < total:
        end = min(cuts[-1] + chunk_entries, total)
        # advance to the next column boundary
        while end < total and cols_flat[end] == cols_flat[end - 1]:
            end += 1
        cuts.append(end)
    n_chunks = len(cuts) - 1

    width = max(cuts[i + 1] - cuts[i] for i in range(n_chunks))
    rows_c = np.full((n_chunks, width), n, dtype=np.int32)  # pad -> trash row
    dcol_c = np.full((n_chunks, width), width, dtype=np.int32)  # pad -> trash col
    for c in range(n_chunks):
        lo, hi = cuts[c], cuts[c + 1]
        if hi == lo:
            continue
        seg_cols = cols_flat[lo:hi]
        # dense relabel within the chunk (seg_cols is sorted)
        is_first = np.concatenate([[True], seg_cols[1:] != seg_cols[:-1]])
        dcol = np.cumsum(is_first) - 1
        rows_c[c, : hi - lo] = rows_flat[lo:hi]
        dcol_c[c, : hi - lo] = dcol.astype(np.int32)
    return rows_c, dcol_c


# row-block size of the triangular matmul schedule; must divide the
# _ROW_BUCKET-padded row count, so it equals the bucket quantum
_TRI_BLOCK = 256


def _tri_blocks(n_pad: int) -> int:
    return -(-n_pad // _TRI_BLOCK)


@functools.partial(jax.jit, static_argnames=("n", "compact_out", "triangular"))
def _accumulate_chunks(rows_c, dcol_c, *, n: int, compact_out: bool, triangular: bool = True):
    """lax.scan over chunks: inter += I@I.T — the [n, n] intersection-count
    matrix (exact: 0/1 bf16 products, f32 accumulation). With `compact_out`
    the result is cast to int16 (counts <= sketch size < 2^15): the
    download is halved and the Jaccard math runs on host instead (whether
    the host link is the bottleneck on the current machine is not
    measured — ROADMAP S1/D2).

    `triangular` (default): intersection counts are symmetric, so each
    chunk contributes only the canonical (bi <= bj) row blocks — per block
    row one rect dot [_TRI_BLOCK, W] x [W, n - lo] against the remaining
    columns (~half the MXU FLOPs at 8+ blocks). The strictly-lower blocks
    stay zero; the HOST mirrors them in after the single result transfer
    (:func:`_mirror_lower`) — bit-equal to the full matmul (0/1 products
    accumulate to exact small integers in f32, order-independent)."""
    width = rows_c.shape[1]

    def step(inter, chunk):
        rows, dcol = chunk
        ind = (
            jnp.zeros((n + 1, width + 1), jnp.bfloat16)
            .at[rows.astype(jnp.int32), dcol.astype(jnp.int32)]
            .set(1.0)
        )
        ind = ind[:n, :width]
        # NT-layout dot_general: contract the W axis of both operands
        # directly (measured faster than scattering a second transposed
        # indicator for the MXU-native NN layout)
        if triangular:
            for lo in range(0, n, _TRI_BLOCK):
                part = jax.lax.dot_general(
                    ind[lo : lo + _TRI_BLOCK],
                    ind[lo:],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                inter = inter.at[lo : lo + _TRI_BLOCK, lo:].add(part)
        else:
            inter = inter + jax.lax.dot_general(
                ind, ind, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
        return inter, None

    inter, _ = jax.lax.scan(
        step, jnp.zeros((n, n), jnp.float32), (rows_c, dcol_c)
    )
    return inter.astype(jnp.int16) if compact_out else inter


def _mirror_lower(mat: np.ndarray) -> np.ndarray:
    """Host half of the triangular schedule at this module's block size —
    ONE mirror implementation serves every triangular matmul
    (ops/containment.py owns it)."""
    from drep_tpu.ops.containment import mirror_lower_blocks

    return mirror_lower_blocks(mat, _TRI_BLOCK)


def _below_counts(ids: np.ndarray, counts: np.ndarray, thresholds: np.ndarray) -> np.ndarray:
    """below[i, j] = |S_i <= t_j|, exact, via one searchsorted per sorted
    row. Host-side on purpose: it overlaps the async device scan.

    The overlap claim, with numbers:
    this pass measures 0.68 s at n=4096 and 4.9 s at n=16384 (s=1000,
    single core) — O(n^2 log s), so ~17 s at the ~30k matmul-budget
    ceiling. The device scan it overlaps does 2·n^2·chunk_entries FLOPs
    per chunk over ~n·s/chunk_entries chunks = 2·n^3·s MACs total — at
    n=30k that is tens of PFLOP, minutes of MXU time. The host pass stays
    an order of magnitude under the device work it hides behind at every
    size the budget admits. (A vectorized rank-histogram rewrite was
    benchmarked 2.8x SLOWER at n=16384 — the per-threshold column gather
    is cache-hostile — hence the plain loop.)
    """
    n = ids.shape[0]
    below = np.empty((n, n), np.float32)
    for i in range(n):
        below[i] = np.searchsorted(ids[i, : counts[i]], thresholds, side="right")
    return below


def _jaccard_host(inter: np.ndarray, below: np.ndarray, counts: np.ndarray, t: np.ndarray, k: int):
    """Common-threshold Jaccard + Mash distance, on host: the [N, N]
    elementwise math is a few hundred MFLOP, far cheaper than shipping
    `below` up and two result matrices back over a slow host<->device link.
    u = restricted union at t_min = min(t_i, t_j); the side with the larger
    threshold is a complete sample below t_min, the other contributes its
    below-threshold count."""
    nf = counts.astype(np.float32)
    inter = inter.astype(np.float32)
    t_i = t[:, None]
    t_j = t[None, :]
    u = np.where(
        t_j < t_i,
        below + nf[None, :] - inter,
        nf[:, None] + below.T - inter,
    )
    j = np.where(u > 0, inter / np.maximum(u, 1.0), 0.0).astype(np.float32)
    dist = mash_distance_from_jaccard(j, k, xp=np).astype(np.float32)
    return dist, j


_ROW_BUCKET = 256  # row-count quantum: caps XLA compilations across calls
_WIDTH_BUCKET = 1024  # chunk-width quantum (chunk widths are data-dependent)
_NCHUNK_BUCKET = 8  # chunk-count quantum


def _bucket_chunks(rows_c: np.ndarray, dcol_c: np.ndarray, n_pad: int):
    """Pad chunk tensors to quantized (n_chunks, width) so the jitted scan
    compiles once per bucket, not once per dataset. Trash entries scatter
    to (row n_pad, col W_b), outside the [:n, :width] slice the matmul sees.
    """
    n_chunks, width = rows_c.shape
    w_b = -(-width // _WIDTH_BUCKET) * _WIDTH_BUCKET
    c_b = -(-n_chunks // _NCHUNK_BUCKET) * _NCHUNK_BUCKET
    out_rows = np.full((c_b, w_b), n_pad, dtype=rows_c.dtype)
    out_dcol = np.full((c_b, w_b), w_b, dtype=dcol_c.dtype)
    out_rows[:n_chunks, :width] = rows_c
    # remap the old per-dataset trash column (== width) to the bucketed one
    out_dcol[:n_chunks, :width] = np.where(dcol_c == width, w_b, dcol_c)
    return out_rows, out_dcol


def all_vs_all_mash_matmul(
    packed: PackedSketches,
    k: int = 21,
    chunk_entries: int = DEFAULT_CHUNK_ENTRIES,
    triangular: bool = True,
) -> tuple[np.ndarray, np.ndarray]:
    """Full [N, N] (dist, jaccard) via the MXU estimator. `triangular`
    (default) computes only canonical (bi <= bj) intersection blocks and
    mirrors the rest on host — bit-equal, ~half the MXU FLOPs; False keeps
    the full-grid scan as the equality reference."""
    n = packed.n
    if n == 0:
        return np.zeros((0, 0), np.float32), np.zeros((0, 0), np.float32)
    # bucket the row count so repeated calls (multiround chunks, resumed
    # runs) reuse the compiled scan instead of recompiling per shape
    ids, counts = pad_packed_rows(packed.ids, packed.counts, _ROW_BUCKET)
    if int(counts.max()) == 0:
        # all sketches empty: maximal distance everywhere (matches the sort
        # path), identity on the diagonal
        dist = np.ones((n, n), np.float32)
        jac = np.zeros((n, n), np.float32)
        np.fill_diagonal(dist, 0.0)
        np.fill_diagonal(jac, 1.0)
        return dist, jac
    n_pad = ids.shape[0]
    # per-genome bottom-s threshold = largest valid id in the row
    t = np.where(
        counts > 0, ids[np.arange(n_pad), np.maximum(counts - 1, 0)], np.int32(-1)
    ).astype(np.int32)
    rows_c, dcol_c = _build_chunks(ids, chunk_entries)
    rows_c, dcol_c = _bucket_chunks(rows_c, dcol_c, n_pad)
    # minimize link traffic: int16 chunk tensors up (when shapes fit), a
    # single int16 count matrix down, everything elementwise on host
    width = rows_c.shape[1]
    compact = n_pad < 2**15 and width + 1 < 2**15 and int(counts.max()) < 2**15
    if compact:
        rows_c = rows_c.astype(np.int16)
        dcol_c = dcol_c.astype(np.int16)
    # dispatch the device scan first (async), then fill `below` on host
    # while the MXU works — the searchsorted pass costs ~zero wall-clock
    inter_dev = _accumulate_chunks(
        jnp.asarray(rows_c), jnp.asarray(dcol_c), n=n_pad, compact_out=compact,
        triangular=triangular,
    )
    below = _below_counts(ids, counts, t)
    # np.array (not asarray): the host mirror mutates, and a device
    # array's __array__ view is not guaranteed writable
    inter_host = _mirror_lower(np.array(inter_dev)) if triangular else np.asarray(inter_dev)
    from drep_tpu.utils.profiling import counters

    nb = _tri_blocks(n_pad)
    counters.add_tiles(
        "primary_compare",
        computed=nb * (nb + 1) // 2 if triangular else nb * nb,
        total=nb * nb,
    )
    dist, jac = _jaccard_host(inter_host, below, counts, t, k=k)
    dist = dist[:n, :n]
    jac = jac[:n, :n]
    np.fill_diagonal(dist, 0.0)
    np.fill_diagonal(jac, 1.0)
    return dist, jac

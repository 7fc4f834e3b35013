"""Device all-vs-all MinHash (Mash) distance — the `jax_mash` primary engine.

Replaces the reference's `mash sketch` + `mash paste`/`mash dist` subprocess
pipeline (drep/d_cluster/external.py::run_MASH, SURVEY.md §3.2 hot loop #1;
reference mount empty) with:

1. host: uint64 hash sketches -> dense **int32 id space** (ranks in one
   global sorted vocabulary). TPUs have no native uint64; instead of paired
   uint32 lanes we exploit that only *equality and order* of hashes matter,
   so a monotone uint64->int32 rank map is exact and loses nothing.
2. device: for each genome pair, the proper Mash estimator — Jaccard from
   the bottom-``s`` of the *union* of the two sketches — computed with
   fixed-shape sort/cumsum (jit/vmap/MXU-tiling friendly, no data-dependent
   shapes), vmapped over [tile_i, tile_j] blocks.

Distance: ``d = -ln(2j / (1+j)) / k`` (the Mash distance), clipped to [0, 1].
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from drep_tpu.utils.hosttools import usable_cores as _usable_cores

PAD_ID = np.int32(2**31 - 1)  # sorts after every real id; never counted
U16_PAD = np.uint16(0xFFFF)  # pad sentinel of link-compressed uint16 id packs


def pad_sentinel(dtype):
    """THE pad value for an id matrix of `dtype` — one rule for every
    module that fills, pads, or masks id rows (int32/PAD_ID is the kernel
    contract; uint16/U16_PAD is the link-compressed layout that device
    code widens via :func:`widen_ids_device` before use)."""
    return U16_PAD if np.dtype(dtype) == np.uint16 else PAD_ID


def widen_ids_device(x):
    """uint16 id rows -> the int32/PAD_ID contract, ON DEVICE (inside
    jit, after the half-size host->device transfer). int32 passes
    through untouched. The ONE widen shared by every device consumer."""
    if x.dtype == jnp.uint16:
        return jnp.where(x == jnp.uint16(U16_PAD), jnp.int32(PAD_ID), x.astype(jnp.int32))
    return x


def require_int32_ids(ids, where: str) -> None:  # np OR device array (dtype-only)
    """Loud boundary check for paths that do NOT widen: a uint16 pack
    reaching them would read its 0xFFFF pads as real ids and produce
    silently wrong counts (pads matching pads inflate every
    intersection)."""
    if ids.dtype != np.int32:
        raise TypeError(
            f"{where} requires int32/PAD_ID id rows, got {ids.dtype}: uint16 "
            "link-compressed packs are consumed only by the one-shot matmul "
            "and stacked-bucket paths, which widen on device"
        )


@dataclass
class PackedSketches:
    """Fixed-shape device-ready sketch pack.

    ids:    [N, s] int32, each row ascending, padded with PAD_ID
    counts: [N]    int32, number of valid entries per row
    names:  list of N genome names (host-side bookkeeping)
    """

    ids: np.ndarray
    counts: np.ndarray
    names: list[str]

    @property
    def n(self) -> int:
        return self.ids.shape[0]

    @property
    def sketch_size(self) -> int:
        return self.ids.shape[1]


def _fill_padded_rows(ids: np.ndarray, ranks: np.ndarray, lens: np.ndarray) -> None:
    """Write ragged rank rows into the preallocated padded matrix (or a
    row slice of one) `ids`: row r gets the next `lens[r]` of `ranks`, cast
    to the matrix's dtype on the way; what lies past a row's length keeps
    its pad value. THE one way to fill a packed matrix (this pack and both
    scaled packs of ops/containment.py): a contiguous slice copy per row is
    a memcpy (18 ms for 512 rows of 26k ranks), where the `np.repeat` /
    `cumsum` / `arange` coordinates and fancy-index scatter it replaces
    wrote two int64 numbers per rank before the rank (~0.3 s for the same
    rows, a third of the cluster-local pack: ISSUE 25)."""
    o = 0
    for row, n in zip(ids, lens):
        row[:n] = ranks[o : o + n]
        o += n


# hashes a thread of the rank kernel should have to itself: starting one
# costs more than ranking fewer. On the chip host (13 cores; PERF.md section
# 6, PR 40) 5,000 hashes took 0.2 ms on one thread and 3.6 ms on six, 1e5
# 4.4 against 4.8 ms, 3.84e5 18.7 against 8.1 ms on four
RANK_HASHES_PER_THREAD = 1 << 16


def rank_route(hashes: int, workers: int = 1) -> tuple[str, int]:
    """(path, threads) by which :func:`pack_sketches` ranks `hashes` uint64
    hashes when its caller grants `workers`: `native` (native/rank.cc) where
    the library is to be had, on `workers` threads capped by the usable
    cores and by `RANK_HASHES_PER_THREAD`, else `numpy` on one. A function
    of the hash count and the host, nothing else, so that a caller can say
    beforehand what the pack will do."""
    from drep_tpu import native

    if hashes == 0 or native.get_library() is None:
        return "numpy", 1
    return "native", max(1, min(int(workers), _usable_cores(), hashes // RANK_HASHES_PER_THREAD))


def rank_rows_padded(rows: list[np.ndarray], width: int, threads: int) -> np.ndarray:
    """The padded int32 id matrix [len(rows), width] of uint64 `rows` by
    native/rank.cc on `threads` threads (the `native` route of
    :func:`rank_route`): a hash's id is its rank among the distinct hashes
    of ALL rows, PAD_ID past a row's length. The kernel reads the rows
    where they lie and writes the whole matrix, padding included; it
    counts the vocabulary first, and a vocabulary at the int32 limit is
    refused in NumPy's words."""
    from drep_tpu.native import rank_rows_native

    ids = np.empty((len(rows), width), dtype=np.int32)
    limit = np.iinfo(np.int32).max
    if rank_rows_native(rows, ids, PAD_ID, threads, limit) >= limit:
        raise ValueError("id space overflow: >2^31 distinct sketch hashes")
    return ids


def pack_sketches(
    sketches: list[np.ndarray], names: list[str], sketch_size: int, workers: int = 1
) -> PackedSketches:
    """uint64 bottom-k sketches (sorted unique) -> padded int32 id matrix.
    `workers` is the thread width the native rank kernel may take (the
    job's `-p`); the matrix is the same bytes at any width and by the NumPy
    lines below, which serve without the library and are the tests' oracle."""
    if len(sketches) != len(names):
        raise ValueError("sketches and names length mismatch")
    trimmed = [s[:sketch_size] for s in sketches]
    lens = np.array([len(s) for s in trimmed], dtype=np.int64)
    path, threads = rank_route(int(lens.sum()), workers)
    # (rows of another dtype are NumPy's to promote and order, as they were)
    if path == "native" and all(s.dtype == np.uint64 for s in trimmed):
        ids = rank_rows_padded(trimmed, sketch_size, threads)
        return PackedSketches(ids=ids, counts=lens.astype(np.int32), names=list(names))
    ids = np.full((len(trimmed), sketch_size), PAD_ID, dtype=np.int32)
    flat = np.concatenate(trimmed) if trimmed else np.empty(0, np.uint64)
    if flat.size:
        # the monotone rank map without a search: a hash's rank in the
        # sorted vocabulary is the number of run starts at or before it in
        # the sorted hashes, less one. `np.unique` + `np.searchsorted` gave
        # the same ranks by one binary search a hash, every level a cache
        # miss once the vocabulary outgrows the cache: 4.8 of 5.8 s at 10^7
        # hashes of which 9M distinct (ISSUE 28). Equal hashes get equal
        # ranks whatever order the sort leaves them in, so it need not be
        # stable; the default kind is the quicker one (PERF.md section 6)
        order = np.argsort(flat)
        srt = flat[order]
        first = np.ones(len(srt), dtype=bool)
        np.not_equal(srt[1:], srt[:-1], out=first[1:])
        # the vocabulary's size, counted before any int32 could wrap
        if np.count_nonzero(first) >= np.iinfo(np.int32).max:
            raise ValueError("id space overflow: >2^31 distinct sketch hashes")
        rank_of_sorted = np.cumsum(first, dtype=np.int32)
        rank_of_sorted -= 1
        ranks = np.empty(len(flat), dtype=np.int32)
        ranks[order] = rank_of_sorted
        _fill_padded_rows(ids, ranks, lens)
    return PackedSketches(ids=ids, counts=lens.astype(np.int32), names=list(names))


def pad_packed_rows(ids: np.ndarray, counts: np.ndarray, multiple: int):
    """Pad a packed sketch matrix to a row multiple: PAD_ID rows, zero counts.

    The single shared implementation of the padding invariant used by the
    tiled single-device loops and the mesh-sharded path alike.
    """
    n = ids.shape[0]
    nt = -(-n // multiple) * multiple
    if nt == n:
        return ids, counts
    # uint16 packs (the cluster-local batched secondary's link-compressed
    # layout) pad with their own sentinel — PAD_ID overflows 16 bits
    pad_ids = np.full((nt, ids.shape[1]), pad_sentinel(ids.dtype), dtype=ids.dtype)
    pad_ids[:n] = ids
    pad_counts = np.zeros(nt, dtype=counts.dtype)
    pad_counts[:n] = counts
    return pad_ids, pad_counts


def _pair_shared(a: jnp.ndarray, b: jnp.ndarray, na: jnp.ndarray, nb: jnp.ndarray):
    """Mash estimator core for one pair of sorted padded id rows.

    Returns (shared, s_use): `shared` = number of hashes present in BOTH
    sketches among the bottom-`s_use` distinct hashes of the union.

    Implementation notes, both deliberate:
    - merge, don't sort: the rows are already sorted, so a bitonic merge
      (ops/merge.py, O(log S) min/max stages) replaces the O(log^2 S)
      full-sort network with identical output.
    - no gathers: a searchsorted/binary-search alternative (asymptotically
      cheaper) measured ~70x SLOWER on v5e — batched gathers serialize on
      the scalar unit, while the fused merge/cumsum chain stays on the VPU.
    """
    from drep_tpu.ops.merge import merge_sorted_rows, next_pow2

    s = a.shape[0]
    s2 = next_pow2(s)
    if s2 != s:
        pad = jnp.full((s2 - s,), PAD_ID, dtype=a.dtype)
        a = jnp.concatenate([a, pad])
        b = jnp.concatenate([b, pad])
    x = merge_sorted_rows(a, b)
    is_real = x != PAD_ID
    dup = jnp.concatenate([jnp.zeros(1, bool), x[1:] == x[:-1]]) & is_real
    start = is_real & ~dup
    rank = jnp.cumsum(start)  # distinct rank; a dup shares its start's rank
    s_use = jnp.minimum(jnp.minimum(na, nb), s).astype(jnp.int32)
    shared = jnp.sum((dup & (rank <= s_use)).astype(jnp.int32))
    return shared, s_use


def mash_distance_from_jaccard(j, k: int, xp=jnp):
    """d = -ln(2j / (1+j)) / k, clipped to [0, 1]; j == 0 -> 1.

    `xp` selects the array module: jnp on device paths, np for host-side
    estimators (one formula, so the estimators can never drift apart)."""
    jj = xp.maximum(j, 1e-30)  # keep log() off 0 even where the branch loses
    d = xp.where(j > 0.0, -xp.log(2.0 * jj / (1.0 + jj)) / k, 1.0)
    return xp.clip(d, 0.0, 1.0)


@functools.partial(jax.jit, static_argnames=("k",))
def mash_distance_tile(a_ids, a_counts, b_ids, b_counts, *, k: int = 21):
    """(distance, jaccard) tiles [Ta, Tb] between two blocks of packed sketches.

    a_ids [Ta, s] int32 sorted+padded, a_counts [Ta]; likewise b. Pure
    fixed-shape ops -> vmap twice; XLA fuses the sort/cumsum chain per pair.
    """

    def one_pair(a, na, b, nb):
        shared, s_use = _pair_shared(a, b, na, nb)
        j = jnp.where(s_use > 0, shared / jnp.maximum(s_use, 1), 0.0)
        return mash_distance_from_jaccard(j, k), j

    row = jax.vmap(one_pair, in_axes=(None, None, 0, 0))
    return jax.vmap(row, in_axes=(0, 0, None, None))(a_ids, a_counts, b_ids, b_counts)


def all_vs_all_mash(
    packed: PackedSketches,
    k: int = 21,
    tile: int = 256,
) -> tuple[np.ndarray, np.ndarray]:
    """Full [N, N] Mash distance + Jaccard matrices, computed in device tiles.

    Host-side tiling loop: pads N up to a multiple of `tile` so every device
    call has the same static shape (one XLA compilation, cached). For very
    large N use drep_tpu.parallel.allpairs (mesh-sharded) instead.
    """
    from drep_tpu.utils.profiling import counters

    n = packed.n
    ids, counts = pad_packed_rows(packed.ids, packed.counts, tile)
    nt = ids.shape[0]
    nb = nt // tile
    # upper-triangle tile walk (j0 >= i0): Mash distance is symmetric, the
    # lower blocks below are host-transposed copies — record the schedule
    counters.add_tiles("primary_compare", computed=nb * (nb + 1) // 2, total=nb * nb)

    dist = np.ones((nt, nt), dtype=np.float32)
    jac = np.zeros((nt, nt), dtype=np.float32)
    for i0 in range(0, nt, tile):
        for j0 in range(i0, nt, tile):
            d, j = mash_distance_tile(
                ids[i0 : i0 + tile],
                counts[i0 : i0 + tile],
                ids[j0 : j0 + tile],
                counts[j0 : j0 + tile],
                k=k,
            )
            d = np.asarray(d)
            j = np.asarray(j)
            dist[i0 : i0 + tile, j0 : j0 + tile] = d
            jac[i0 : i0 + tile, j0 : j0 + tile] = j
            if j0 != i0:
                dist[j0 : j0 + tile, i0 : i0 + tile] = d.T
                jac[j0 : j0 + tile, i0 : i0 + tile] = j.T
    dist = dist[:n, :n]
    jac = jac[:n, :n]
    np.fill_diagonal(dist, 0.0)
    np.fill_diagonal(jac, 1.0)
    return dist, jac

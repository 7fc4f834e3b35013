"""Host-side range partitioning of sorted sketch-id rows.

Intersection counts are exactly additive over disjoint hash ranges:
|A ∩ B| = Σ_r |A∩[b_r,b_{r+1}) ∩ B∩[b_r,b_{r+1})|. That one identity
extends the fixed-budget device kernel to production sketch widths
(4 Mb genomes at the default scale=200 give ~20k-wide scaled sketches,
far past any single-call indicator budget — SURVEY.md §7 hard part (c);
reference mount empty, no counterpart to cite): the MXU indicator matmul
(ops/containment.py) caps m·vocab, so it chunks the *vocabulary*:
containment._stacked_vocab_chunks repacks the per-chunk rows on host with
this module's bucket_starts/repack_bucket, ships ONE stacked tensor, and
runs the same indicator matmul per chunk. The federated index shards its
band-code space with the same partition (index/federation.py).

Rows hold DISTINCT sorted ids (sketches are sets), so a bucket covering
`w` consecutive id values can contribute at most `w` entries per row —
the adaptive splitter below always terminates.

All work here is numpy on host: one bincount pass for the per-bucket
histogram, one flat gather/scatter per bucket for the repack (the same
vectorized-repack idiom as ops/minhash.py::pack_sketches — per-row
Python loops were a measured hot spot at production batch counts).
"""

from __future__ import annotations

from collections.abc import Iterator

import numpy as np

from drep_tpu.ops.merge import next_pow2
from drep_tpu.ops.minhash import PAD_ID, pad_sentinel

MIN_BUCKET_WIDTH = 128  # lane width — never repack below one full lane row

# raw uint64 sketch hashes -> int32 band codes: drop 34 low bits so the
# code space is 2^30 (< PAD_ID, so the pad sentinel can never collide
# with a real code). The map is monotone and many-to-one: two sketches
# sharing a hash ALWAYS share the code (the recall direction the
# federated boundary join leans on); distinct hashes may merge into one
# code (the false-positive direction, paid in candidate count only).
HASH_CODE_SHIFT = 34

# coarse ROUTING-summary code space (ISSUE 14, the streaming federated
# classify router): top 16 bits of the raw hash — a further monotone
# many-to-one coarsening of the band code (coarse = band >> 14), so the
# recall chain composes: a retained pair shares a raw hash => shares a
# band code => shares a coarse code. 2^16 codes pack into an 8 KiB
# bitmap per partition — small enough to keep EVERY partition's summary
# resident while the sketch payloads themselves stay lazily loaded.
ROUTE_SUMMARY_BITS = 16


def hash_code_matrix(hash_rows: list[np.ndarray], shift: int = HASH_CODE_SHIFT) -> np.ndarray:
    """Sorted uint64 hash rows (raw bottom sketches) -> one [N, W] int32
    PAD-padded matrix of DISTINCT sorted band codes per row.

    This is the federation boundary join's front door (index/
    federation.py): partition stores pack their own LOCAL rank spaces
    (ops/minhash.pack_sketches ranks are pack-relative, so two
    partitions' packed ids can never be joined), but the raw hashes are
    global — shifting them into a shared 2^30 code space gives every
    partition the same monotone banding, and the result is exactly the
    sorted-distinct-id layout :func:`partition_by_range` shards.
    """
    n = len(hash_rows)
    codes = [
        np.unique((np.asarray(r, np.uint64) >> np.uint64(shift)).astype(np.int32))
        for r in hash_rows
    ]
    width = max((len(c) for c in codes), default=0)
    out = np.full((n, max(1, width)), PAD_ID, dtype=np.int32)
    for i, c in enumerate(codes):
        out[i, : len(c)] = c
    return out


def coarse_codes(hash_row: np.ndarray, bits: int = ROUTE_SUMMARY_BITS) -> np.ndarray:
    """Distinct sorted coarse routing codes (top `bits` bits) of one raw
    uint64 hash row — the query side of the partition routing summary."""
    return np.unique(
        (np.asarray(hash_row, np.uint64) >> np.uint64(64 - bits)).astype(np.int64)
    )


def code_summary_bitmap(
    hash_rows: list[np.ndarray], bits: int = ROUTE_SUMMARY_BITS
) -> np.ndarray:
    """One packed-uint64 bitmap over the 2^bits coarse code space with a
    set bit for every coarse code present in ANY of `hash_rows` — a
    partition's routing summary. Exact (no false negatives): membership
    here is a superset test, never a probabilistic filter, so the
    streaming router keeps the boundary join's recall-1.0 chain."""
    bm = np.zeros((1 << bits) >> 6, np.uint64)
    for r in hash_rows:
        c = coarse_codes(r, bits)
        np.bitwise_or.at(
            bm, c >> 6, np.left_shift(np.uint64(1), (c & 63).astype(np.uint64))
        )
    return bm


def bitmap_contains_any(bitmap: np.ndarray, codes: np.ndarray) -> bool:
    """Does the summary bitmap hold ANY of the (distinct int64) coarse
    codes? The router's per-(query, partition) consult decision."""
    if not len(codes):
        return False
    codes = np.asarray(codes, np.int64)
    hits = bitmap[codes >> 6] & np.left_shift(
        np.uint64(1), (codes & 63).astype(np.uint64)
    )
    return bool(np.any(hits != 0))


def vocab_extent(ids: np.ndarray) -> int:
    """1 + max real id (0 when everything is padding) — THE extent rule:
    the range partitioner, the matmul vocab bucketing and the chunk
    geometry all derive from this one definition.
    uint16 packs (link-compressed cluster-local layout) use their own pad
    sentinel."""
    valid = ids != pad_sentinel(ids.dtype)
    return int(ids[valid].max()) + 1 if valid.any() else 0


def _vocab_extent(mats: list[np.ndarray]) -> int:
    return max((vocab_extent(m) for m in mats), default=0)


def bucket_starts(ids: np.ndarray, chunk: int, n_buckets: int) -> np.ndarray:
    """Per-row boundary positions for equal-width id ranges.

    ids [N, S] sorted PAD-padded; range r covers [r*chunk, (r+1)*chunk).
    Returns int64 [N, n_buckets+1]: starts[i, r] = index of row i's first
    element >= r*chunk, so bucket r spans starts[:, r]..starts[:, r+1] and
    its counts are np.diff(starts). Rows are sorted with PAD_ID (int32
    max, >= every boundary) at the tail, so one searchsorted per row over
    the ~dozens of boundaries replaces a bincount pass over every element
    (measured 0.39 s -> ~5 ms at [512, 32768] production shape).
    """
    bounds = np.minimum(np.arange(1, n_buckets + 1, dtype=np.int64) * chunk, PAD_ID)
    starts = np.empty((ids.shape[0], n_buckets + 1), dtype=np.int64)
    starts[:, 0] = 0
    for i in range(ids.shape[0]):
        starts[i, 1:] = np.searchsorted(ids[i], bounds, side="left")
    return starts


def bucket_histogram(ids: np.ndarray, chunk: int, n_buckets: int) -> np.ndarray:
    """Per-row element counts for equal-width id ranges (diff of
    :func:`bucket_starts`). Kept as the partitioners' shared counting rule."""
    return np.diff(bucket_starts(ids, chunk, n_buckets), axis=1)


def repack_bucket(
    ids: np.ndarray,
    starts: np.ndarray,
    cnt: np.ndarray,
    width: int,
    rebase: int = 0,
) -> np.ndarray:
    """Extract one range bucket into a fresh [N, width] PAD-padded matrix.

    `starts[i]`/`cnt[i]` delimit row i's (contiguous — rows are sorted)
    slice belonging to the bucket; `rebase` is subtracted from real values
    (the matmul path rebases each vocab chunk to origin 0).
    """
    n = ids.shape[0]
    out = np.full((n, width), PAD_ID, dtype=np.int32)
    total = int(cnt.sum())
    if total == 0:
        return out
    rows = np.repeat(np.arange(n), cnt)
    offs = np.concatenate([[0], np.cumsum(cnt)[:-1]])
    local = np.arange(total) - np.repeat(offs, cnt)
    src_col = np.repeat(starts, cnt) + local
    out[rows, local] = ids[rows, src_col] - rebase
    return out


def partition_by_range(
    mats: list[np.ndarray],
    max_count: int,
    rebase: bool = False,
) -> Iterator[tuple[int, list[np.ndarray]]]:
    """Split sorted PAD-padded id matrices into shared disjoint id-range
    buckets, each repacked to width <= max_count.

    Yields (chunk_origin, [bucket matrix per input]) for every non-empty
    bucket; widths are pow2-bucketed (>= MIN_BUCKET_WIDTH, one XLA
    compilation per distinct width, cf. containment._pow2_bucket rationale).
    All inputs share one boundary set, so cross-matrix intersections stay
    exact. Empty-range buckets are skipped — hash ids are dense ranks, so
    with uniform hashes the count histogram is tight around mean density.

    The splitter starts at the optimistic bucket count (longest row /
    max_count) and doubles until every per-row bucket count fits; ranges of
    width <= max_count trivially fit (rows hold distinct ids), so the loop
    is bounded by log2(vocab/max_count) extra histogram passes.
    """
    if max_count < MIN_BUCKET_WIDTH:
        raise ValueError(f"max_count {max_count} below lane width {MIN_BUCKET_WIDTH}")
    if max_count & (max_count - 1):
        # widths are pow2-bucketed, so a non-pow2 bound would be silently
        # exceeded (next_pow2(1400) = 2048 > 1500) — callers must get
        # exactly the bound they budgeted for
        raise ValueError(f"max_count {max_count} must be a power of two")
    vocab = _vocab_extent(mats)
    if vocab == 0:
        return
    longest = max(int((m != PAD_ID).sum(axis=1).max()) for m in mats)
    n_buckets = next_pow2(-(-longest // max_count))
    while True:
        chunk = -(-vocab // n_buckets)
        starts = [bucket_starts(m, chunk, n_buckets) for m in mats]
        hists = [np.diff(s, axis=1) for s in starts]
        if max(int(h.max()) for h in hists) <= max_count or chunk <= max_count:
            break
        n_buckets *= 2
    for r in range(n_buckets):
        counts_r = [h[:, r] for h in hists]
        w = max(int(c.max()) for c in counts_r)
        if w == 0:
            continue  # empty across all inputs
        width = max(MIN_BUCKET_WIDTH, next_pow2(w))
        yield (
            r * chunk,
            [
                repack_bucket(m, s[:, r], c, width, rebase=r * chunk if rebase else 0)
                for m, s, c in zip(mats, starts, counts_r)
            ],
        )

"""Bitonic merge of pre-sorted sketch rows — the shared compare-exchange core.

Both all-pairs estimators (the Mash union-bottom-s Jaccard in ops/minhash.py
and the jnp streaming tiles in parallel/streaming.py) need the sorted
merge of two already-sorted hash-id rows. A full ``jnp.sort`` of the
concatenation costs O(log^2 L) compare-exchange stages; but the
concatenation of an ascending row with a reversed ascending row is
*bitonic*, so Batcher's bitonic merge finishes in O(log L) stages — each a
full-width vectorized min/max, which is exactly what the VPU wants.

Replaces nothing in the reference (the reference's merge lives inside Mash's
C++ heap walk, d_cluster/external.py::run_MASH upstream; reference mount
empty) — this is the TPU-native formulation of the same sorted-merge step.

PAD handling: PAD_ID (int32 max) sorts after every real id, so padded rows
stay sorted and pads accumulate at the tail of the merged row.
"""

from __future__ import annotations

import jax.numpy as jnp


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1)."""
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


# cap on tile*tile*(2*next_pow2(width)) elements for one jnp sort-merge tile:
# the merge materializes s32 temps of exactly that shape, and several live at
# once — 2^28 elements is ~1 GB per temp, which measured ~3-4 GB peak on v5e
# (16 GB HBM). Uncapped tiles at production widths hard-OOM the chip (an
# uncapped 128-tile at sketch width 32768 wants ~4.3 GB PER temp). The ONE
# budget rule for every jnp-merge tiling loop (parallel/streaming.py).
SORT_TILE_BUDGET_ELEMS = 1 << 28


def cap_merge_tile(tile: int, width: int) -> int:
    """Largest pow2 tile (>= 8, <= `tile`) whose [tile, tile, 2*next_pow2
    (width)] merge temporaries fit SORT_TILE_BUDGET_ELEMS."""
    merged = 2 * max(128, next_pow2(width))
    cap = int((SORT_TILE_BUDGET_ELEMS / merged) ** 0.5)
    return max(8, min(tile, 1 << (cap.bit_length() - 1)))


def merge_sorted_rows(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Sorted merge of two ascending rows along the last axis.

    a, b: [..., S] ascending (PAD_ID-padded). S must be a power of two —
    callers pad with PAD_ID (``next_pow2``) first; padding keeps rows
    ascending so the bitonic precondition holds. Returns [..., 2S]
    ascending. Identical output to ``jnp.sort(concatenate([a, b]))``.
    """
    s = a.shape[-1]
    if s & (s - 1):
        raise ValueError(f"merge width {s} is not a power of two — pad with PAD_ID first")
    # ascending ++ descending = bitonic
    x = jnp.concatenate([a, jnp.flip(b, axis=-1)], axis=-1)
    length = 2 * s
    d = s
    while d >= 1:
        y = x.reshape(*x.shape[:-1], length // (2 * d), 2, d)
        lo = jnp.minimum(y[..., 0, :], y[..., 1, :])
        hi = jnp.maximum(y[..., 0, :], y[..., 1, :])
        x = jnp.stack([lo, hi], axis=-2).reshape(*x.shape[:-1], length)
        d //= 2
    return x

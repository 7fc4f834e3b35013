"""Operations and bytes a kernel needs, from its shapes: the HBM side of
bench.py's `_merge_roofline`, kept with the benchmark so that no later PR can
change the yardstick. (Its vector-operation count has no published peak to
stand against, and `_tri_matmul_flops` waits for a metric that can name the
one-shot calls' shapes: PERF.md, section 7.)"""

from __future__ import annotations


def next_pow2(x: int) -> int:
    return 1 << (x - 1).bit_length()


def mash_hbm_bytes_per_pair(sketch_size: int, tile: int = 1024) -> float:
    """HBM traffic of the Mash tile kernel per pair: a [tile, s2] int32 block
    of each side is read once per tile of tile x tile pairs, and one float32
    distance is written per pair."""
    s2 = next_pow2(sketch_size)
    return (2 * tile * s2 * 4) / (tile * tile) + 4


def mash_hbm_bound_ns_per_pair(sketch_size: int, peaks: dict) -> float:
    return mash_hbm_bytes_per_pair(sketch_size) / peaks["hbm_bytes_per_s"] * 1e9

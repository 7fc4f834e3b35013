"""Operations and bytes of the vocabulary-chunked secondary call, from the
shapes its counter books (`secondary_chunked_calls` of a job's record): the
LEAST the program must do, so that a share of the roofline cannot read over
100%. Nothing of the program is imported; its block rule is copied here.

An entry sums its calls: `extent` is the vocabulary ids of all of them, and
`hashes` their real ids. A call takes `rows_pad` id rows over a vocabulary of
`extent` ids. It must
write a 0/1 int8 indicator of [rows_pad, extent] once and read it once, read
each real id once, multiply the canonical (bi <= bj) row blocks of the
indicator with its transpose over the whole vocabulary, and write each
canonical int32 count once. Padding slots, a last chunk narrower than the
rest, the per-chunk partial counts and their additions are what the program
does on top of that, not what it must.
"""

from __future__ import annotations

ROW_BLOCKS = 8  # ops/containment.py::tri_row_block: a pow2 row block, 8 block rows,
ROW_BLOCK_MIN = 64  # and never under the smallest row bucket
ID_BYTES = {"uint16": 2, "int32": 4}


def canonical_pairs(rows_pad: int) -> int:
    """Row pairs in the canonical blocks of a [rows_pad, rows_pad] grid."""
    tb = max(ROW_BLOCK_MIN, rows_pad // ROW_BLOCKS)
    blocks = rows_pad // tb
    return tb * tb * blocks * (blocks + 1) // 2


def chunked_macs(call: dict) -> int:
    """int8 multiply-accumulates of one counter entry (all its calls)."""
    return canonical_pairs(call["rows_pad"]) * call["extent"]


def chunked_bytes(call: dict) -> int:
    """HBM bytes of one counter entry: indicator written and read once, ids
    read once, canonical counts written once."""
    indicator = 2 * call["rows_pad"] * call["extent"]
    counts = 4 * canonical_pairs(call["rows_pad"]) * call["calls"]
    return indicator + counts + call["hashes"] * ID_BYTES.get(call["id_dtype"], 4)


def chunked_least_seconds(calls: list[dict], peaks: dict) -> tuple[float, str]:
    """(seconds the calls need at the published peaks, which peak bounds
    them): the larger of operations (two a multiply-accumulate, as the
    published int8 rate counts them) over `int8_ops_per_s` and bytes over
    `hbm_bytes_per_s`."""
    compute = sum(2 * chunked_macs(c) for c in calls) / peaks["int8_ops_per_s"]
    memory = sum(chunked_bytes(c) for c in calls) / peaks["hbm_bytes_per_s"]
    return (compute, "int8") if compute >= memory else (memory, "hbm")

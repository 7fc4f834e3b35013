"""Operations and bytes of the greedy engine's device work, counted from the
WORK and not from how the program lays it out: from `rows`, `extent`,
`hashes` and `compared_pairs` of the record's `secondary_greedy_calls` (one
entry a primary cluster the engine served) and from nothing else. Nothing of
the program is imported.

What a cluster's comparisons need, whatever implements them: each of its
`rows` genomes as a 0/1 int8 indicator over the cluster's vocabulary of
`extent` ids, written once and read once; each of its `hashes` real ids read
once (int32); one int8 multiply-accumulate per id of the extent per pair the
greedy scan consumed (`compared_pairs`: a genome against a representative that
existed at its visit); one int32 count written per consumed pair.

Never read: `rep_tile`, `rep_rows_shipped`, `block_rows`, `blocks`, `v_chunk`,
`chunks`, `widths`, `id_slots`. A block computed against 512 representative
rows of which five are real, a block against itself, an indicator built again
for every tile and chunk, padding slots: all of that is what the program does
on top of the work, so a later change that retiles the engine is judged on a
yardstick it did not move, and no share can pass 100%.
"""

from __future__ import annotations

ID_BYTES = 4  # an id is an int32 rank in the cluster's vocabulary
COUNT_BYTES = 4


def greedy_macs(call: dict) -> int:
    """int8 multiply-accumulates one cluster's consumed pairs need."""
    return call["compared_pairs"] * call["extent"]


def greedy_bytes(call: dict) -> int:
    """HBM bytes one cluster's comparisons need: indicators written and read
    once, real ids read once, one count written a consumed pair."""
    return (2 * call["rows"] * call["extent"] + ID_BYTES * call["hashes"]
            + COUNT_BYTES * call["compared_pairs"])


def greedy_least_seconds(calls: list[dict], peaks: dict) -> tuple[float, str]:
    """(seconds the clusters need at the published peaks, which peak bounds
    them): the larger of operations (two a multiply-accumulate, as the
    published int8 rate counts them) over `int8_ops_per_s` and bytes over
    `hbm_bytes_per_s`."""
    compute = sum(2 * greedy_macs(c) for c in calls) / peaks["int8_ops_per_s"]
    memory = sum(greedy_bytes(c) for c in calls) / peaks["hbm_bytes_per_s"]
    return (compute, "int8") if compute >= memory else (memory, "hbm")

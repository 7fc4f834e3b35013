"""Share of the block bytes the greedy engine put on a device that had
crossed the link before: 1 - (the blocks' id tensors counted once) /
`block_bytes`, from the record's `secondary_greedy_calls`, summed over the
clusters and over the window's jobs. Counted once, a cluster's blocks are
`blocks` x `block_rows` x `widths` int32 ids; `block_bytes` counts every
crossing (a replicated put once a device). On one device a block crosses
once and the share is 0; on a mesh the engine ships a block's chunks again
for every representative tile and twice more for the self comparison. None
for a program whose record has no `block_bytes`."""

ID_BYTES = 4


def read(run: dict):
    once = crossed = 0
    for job in run.get("jobs", []):
        for call in job["record"].get("secondary_greedy_calls") or []:
            if not call.get("block_bytes"):
                continue
            once += ID_BYTES * call["blocks"] * call["block_rows"] * call["widths"]
            crossed += call["block_bytes"]
    return 100.0 * (1.0 - once / crossed) if crossed else None

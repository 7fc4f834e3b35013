"""Host seconds of the primary compare after its results are back:
`primary/assemble` (ring blocks into the matrix and its mirror, a stripe's
edges joined, counts to distances), `primary/publish` (shard and block
saves) and `primary/lsh_join` (the candidate join, where pruning is on).
Median over the window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("primary/assemble", "primary/publish", "primary/lsh_join"))

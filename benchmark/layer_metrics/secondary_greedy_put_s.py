"""Seconds of `secondary/greedy_put`: every put of chunk tensors the greedy
engine's matmul route makes, from the call to the arrays' arrival: a block's
chunks (on one device once a block; on a mesh row-sharded once a
representative tile, and once more row-sharded and once replicated for the
self comparison), and on a mesh the representative tiles (a filled one
replicated once, the trailing one once a block). The spans lie inside
`secondary/greedy_wait` and `secondary/greedy_layout`, whose self seconds
exclude them, so the three readers partition the engine's time. Median over
the window's jobs; None for a program without the span."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("secondary/greedy_put",))

"""Seconds of `secondary/greedy_wait`: a block's transfer, its tiles against
the resident representatives, its self comparison and the blocking readbacks
of the greedy engine; the one-shot calls of the same job stay under
`secondary/wait`. Median over the window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("secondary/greedy_wait",))

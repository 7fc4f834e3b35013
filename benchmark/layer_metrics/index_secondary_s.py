"""Seconds of the `index/secondary` spans in a job's own record, summed: one
call of `secondary_for_cluster` a primary cluster whose member set changed
(`secondary/pack`, `secondary/wait`, `secondary/chunks`, `secondary/post`
inside). Median over the window's jobs; None where the program has no such
span."""
from benchmark import spans


def read(run: dict):
    return spans.seconds(run, "index/secondary")

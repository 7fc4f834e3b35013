"""Seconds of the `index/score` spans in a job's own record, summed: one call
of the choose stage's `score_and_pick` a recomputed cluster and a new
singleton. Median over the window's jobs; None where the program has no such
span."""
from benchmark import spans


def read(run: dict):
    return spans.seconds(run, "index/score")

"""Seconds of `primary/linkage`: hierarchical clustering of the dense
matrix, or sparse UPGMA / connected components over the streamed edges.
Median over the window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("primary/linkage",))

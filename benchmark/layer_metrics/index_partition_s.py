"""Seconds of the `index/partition` span in a job's own record: the components
of the union's edge graph, the dirty ones reclustered, the labels renumbered.
Median over the window's jobs; None where the program has no such span."""
from benchmark import spans


def read(run: dict):
    return spans.seconds(run, "index/partition")

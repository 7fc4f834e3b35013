"""Seconds of `secondary/checkpoint`: opening the per-cluster checkpoint
store, looking every cluster up, saving every cluster. Median over the
window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("secondary/checkpoint",))

"""Seconds of `stage:ingest_or_cache`: in these cells, reading the planted
sketch cache back from the workdir. Median over the window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("stage:ingest_or_cache",))

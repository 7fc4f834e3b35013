"""Seconds of `secondary/wait`: transfer, dispatch and blocking readback of
each one-shot intersection call. Median over the window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("secondary/wait",))

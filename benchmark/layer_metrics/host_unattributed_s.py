"""Seconds inside a job that no named phase covers: the self seconds of the
`job` span and of the spans that only hold other spans (the stages round the
compares, a stripe, a ring step). Median over the window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, phases.CONTAINERS)

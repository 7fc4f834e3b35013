"""Seconds of `primary/wait`: the host blocked on a primary result (a
watchdog-bounded wait, a scalar sync, the readback of a device array).
Median over the window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("primary/wait",))

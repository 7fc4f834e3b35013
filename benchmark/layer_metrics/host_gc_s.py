"""Seconds inside Python's cyclic collector on the main thread: `gc_s` summed
over its phases. Median over the window's jobs; None where the record has no
such field."""

from benchmark import host


def read(run: dict):
    return host.summed(run, lambda _name, ph: ph.get("gc_s"))

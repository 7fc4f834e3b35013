"""Memory the process touched for the first time inside a job: the main
thread's `self_minor_faults` summed over its phases (they add up to the `job`
span's; every thread's faults are in them) times the page size, in GiB. A
floor where the host hands out transparent huge pages (one fault each).
Median over the window's jobs; None where the record has no such field, or
the host's kernel counts no faults (`host._kept`: the chip host's does not,
so no cell lists this metric yet)."""

from benchmark import host


def read(run: dict):
    return host.pages_to_gib(host.summed(run, lambda _name, ph: ph.get("self_minor_faults"), counted=True))

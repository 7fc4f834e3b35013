"""How often the main thread was taken off its core by another process: its
`self_invol_switches` summed over its phases. What a `benchmark` PR on a
cell's spread reads first. Median over the window's jobs; None where the
record has no such field, or the host's kernel keeps no such counts
(`host._kept`: the chip host's does not, so no cell lists this metric yet)."""

from benchmark import host


def read(run: dict):
    return host.summed(run, lambda _name, ph: ph.get("self_invol_switches"), counted=True)

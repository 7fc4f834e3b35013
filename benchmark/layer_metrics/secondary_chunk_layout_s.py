"""Host seconds of `secondary/chunks`: the vocabulary-chunked call's layout
work before anything is shipped (the chunk plans, each row's ids cut at the
chunk boundaries, rebased and repacked into the stacked tensor). Median over
the window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("secondary/chunks",))

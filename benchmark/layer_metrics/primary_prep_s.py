"""Host seconds of the primary compare before a result can be waited for:
`primary/pack` (sketches to padded id rows), `primary/put` (rows to the
device) and `primary/dispatch` (enqueueing the grid, a stripe's tiles or the
ring's steps). Median over the window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("primary/pack", "primary/put", "primary/dispatch"))

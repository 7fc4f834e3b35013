"""Host seconds of the secondary compare's `secondary/pack`: scaled sketches
to cluster-local id rows (`pack_scaled_sketches_clusterlocal`,
`pack_scaled_sketches`) and row padding. Median over the window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("secondary/pack",))

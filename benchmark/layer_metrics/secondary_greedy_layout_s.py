"""Host seconds of `secondary/greedy_layout`: the greedy engine's layout work
before a block's comparison (the cluster's chunk geometry, every block's and
every new representative's ids cut at the chunk boundaries, rebased, repacked
and padded, and the new representatives' shipment to the resident set).
Median over the window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("secondary/greedy_layout",))

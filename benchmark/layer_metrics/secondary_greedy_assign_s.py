"""Host seconds of `secondary/greedy_assign`: the sequential assignment of the
greedy rule and its Ndb rows, in the engine (a span a block and one a cluster)
and on the batched route (a span round each cluster's
`greedy_assign_from_matrices`). Median over the window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("secondary/greedy_assign",))

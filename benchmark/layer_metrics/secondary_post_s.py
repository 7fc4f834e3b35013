"""Host seconds of the secondary compare after its counts are back:
`secondary/post` (mirroring the lower blocks, counts to ANI and coverage,
slicing the diagonal blocks) and `stage:secondary_postprocess` (per-cluster
linkage and Ndb rows). Median over the window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("secondary/post", "stage:secondary_postprocess"))

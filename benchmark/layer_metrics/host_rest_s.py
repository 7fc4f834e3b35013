"""A job's wall less its `primary_compare` and `secondary_compare` stage
seconds: what the workflow round the compare stages costs (loading the sketch
cache, linkage, table writes, evaluation). Median over the window's jobs."""

import statistics


def read(run: dict):
    rest = []
    for j in run.get("jobs", []):
        stages = j["record"].get("stages", {})
        rest.append(j["wall_s"] - sum(stages.get(s, {}).get("seconds", 0.0)
                                      for s in ("primary_compare", "secondary_compare")))
    return statistics.median(rest) if rest else None

"""Seconds of the `primary_compare` stage in a job's own perf_counters.json
(host clock, taken by the program round the stage): median over the window's
jobs."""

import statistics


def read(run: dict):
    secs = [j["record"]["stages"]["primary_compare"]["seconds"] for j in run.get("jobs", [])
            if "primary_compare" in j["record"].get("stages", {})]
    return statistics.median(secs) if secs else None

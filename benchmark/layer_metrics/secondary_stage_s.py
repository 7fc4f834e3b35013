"""Seconds of the `secondary_compare` stage in a job's own record (host clock,
taken by the program round the stage): median over the window's jobs. Nothing
to read where the secondary is off."""

import statistics


def read(run: dict):
    secs = [j["record"]["stages"]["secondary_compare"]["seconds"] for j in run.get("jobs", [])
            if "secondary_compare" in j["record"].get("stages", {})]
    return statistics.median(secs) if secs else None

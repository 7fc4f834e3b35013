"""Milliseconds of `secondary/greedy_wait` a program call of the greedy
engine: the span's seconds over the `device_calls` of the record's
`secondary_greedy_calls` (a call a vocabulary chunk a representative tile, and
the block against itself), job by job; the median over the window's jobs."""

import statistics


def read(run: dict):
    per_job = []
    for job in run.get("jobs", []):
        rec = job["record"]
        wait = (rec.get("phases") or {}).get("secondary/greedy_wait")
        calls = sum(call["device_calls"] for call in rec.get("secondary_greedy_calls") or [])
        if wait and calls:
            per_job.append(1e3 * wait["self_seconds"] / calls)
    return statistics.median(per_job) if per_job else None

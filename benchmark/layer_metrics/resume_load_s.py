"""Seconds a stopped job spends reading back what its earlier attempts
published: the spans `primary/resume_load` (a streaming stripe's shard found,
verified and read, no tile dispatched) and `secondary/resume_load` (a primary
cluster's secondary checkpoint read), whole, summed over a job's attempts
(``resume_jobs.merge_records``). Median over the window's jobs; None for a
program without the spans, and for a job that was never stopped, which opens
neither."""

import statistics

SPANS = ("primary/resume_load", "secondary/resume_load")


def read(run: dict):
    per_job = []
    for job in run.get("jobs", []):
        phases = job["record"].get("phases") or {}
        found = [phases[name]["seconds"] for name in SPANS if name in phases]
        if found:
            per_job.append(sum(found))
    return statistics.median(per_job) if per_job else None

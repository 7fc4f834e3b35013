"""Reading the `phases` section of a job's own record (perf_counters.json):
every span of the program's front door, `Counters.span`, with its seconds,
its self seconds (its duration less what its child spans cover), its calls
and its thread. A phase of the main thread is keyed by its bare name, so the
self seconds of the names below and of every other bare name add up to the
`job` span. A program that has no such section (the parent of the PR that
brought the spans) gives every reader here None."""

from __future__ import annotations

import statistics

# spans that hold other spans: what is left of them is time inside the job
# that no named phase covers
CONTAINERS = ("job", "stage:cluster", "stage:primary_compare", "stage:secondary",
              "stage:secondary_compare", "stripe", "ring_step", "ring_block_recover")
TRACE_PREFIX = "drep:"  # a span's name on the profiler's host plane


def self_seconds(run: dict, names) -> float | None:
    """Self seconds of the named main-thread phases, summed within a job:
    the median over the window's jobs, or None where no job has any of them."""
    per_job = []
    for job in run.get("jobs", []):
        phases = job["record"].get("phases") or {}
        found = [phases[n]["self_seconds"] for n in names if n in phases]
        if found:
            per_job.append(sum(found))
    return statistics.median(per_job) if per_job else None

"""A span's whole seconds in a job's own record (perf_counters.json,
`phases`): its duration with everything its child spans cover, where
``phases.self_seconds`` gives what no child covers. A stage that holds spans
of its own is read whole here. A program whose record has no such span gives
None."""

from __future__ import annotations

import statistics


def seconds(run: dict, name: str) -> float | None:
    """Seconds of the main thread's span `name`, summed over its calls
    within a job: the median over the window's jobs, or None where no job
    has it."""
    per_job = [job["record"]["phases"][name]["seconds"] for job in run.get("jobs", [])
               if name in (job["record"].get("phases") or {})]
    return statistics.median(per_job) if per_job else None

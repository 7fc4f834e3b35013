"""Reading the `process` section of a job's own record (perf_counters.json):
the program's ledger of what its process had paid when each job began. Its
`first_job` is the process's first job, kept for good with the programs it
built: in a run of this harness the warm-up job, whose own record the job
kinds delete with its work directory. Every job's record carries it, so it is
read from the window's last sound job. A program whose record has no such
section (the parent of the PR that brought the ledger) gives every reader
here None."""

from __future__ import annotations


def first_job(run: dict) -> dict | None:
    """``{verb, began_at_s, bring_up_s, job_s, compile}`` of the process's
    first job: marks in seconds since the process started, `compile` the
    totals (`programs`, `trace_s`, `lower_s`, `backend_compile_s`,
    `cache_load_s`, `cache_hits`, `cache_misses`) with `by_program` and
    `by_span`."""
    jobs = run.get("jobs") or []
    if not jobs:
        return None
    return (jobs[-1]["record"].get("process") or {}).get("first_job")


def compiled(run: dict, *names: str) -> float | None:
    """The named totals of the first job's `compile`, summed."""
    job = first_job(run)
    return sum(job["compile"][n] for n in names) if job else None

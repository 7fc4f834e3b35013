"""What being first costs: the warm-up job's `job_s` less the median `job`
span of the window's jobs, which do the same work on warm programs. It holds
every program traced, lowered, compiled or loaded, and whatever else a first
job pays that raises no compile event (first touches of the backend, page
cache, lazy imports)."""

from benchmark import setup_ledger, spans


def read(run: dict):
    job = setup_ledger.first_job(run)
    warm = spans.seconds(run, "job")
    return job["job_s"] - warm if job and warm is not None else None

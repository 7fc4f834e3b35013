"""Seconds from the process's start to the entry of the warm-up job's
bring-up (`process.first_job.began_at_s`): the interpreter, the imports, the
backend's start, the harness's device gate and its planting of the cell's
data, undivided. With `setup_first_job_s` it adds up to `setup_s` less the
removal of the warm-up job's work directory."""

from benchmark import setup_ledger


def read(run: dict):
    job = setup_ledger.first_job(run)
    return job["began_at_s"] if job else None

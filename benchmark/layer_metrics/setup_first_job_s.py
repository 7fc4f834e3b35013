"""Seconds of the process's first job, the warm-up job: its bring-up, which
no span covers (compile cache, distributed runtime, work directory, logger),
and its `job` span to the ledger's entry (`process.first_job.bring_up_s +
job_s`)."""

from benchmark import setup_ledger


def read(run: dict):
    job = setup_ledger.first_job(run)
    return job["bring_up_s"] + job["job_s"] if job else None

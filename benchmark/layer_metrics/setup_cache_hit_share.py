"""Share of the warm-up job's compile requests that the persistent compile
cache answered: hits over hits and misses of `process.first_job.compile`. A
miss is a request that asked the cache and compiled. None where no request
used the cache."""

from benchmark import setup_ledger


def read(run: dict):
    job = setup_ledger.first_job(run)
    if not job:
        return None
    asked = job["compile"]["cache_hits"] + job["compile"]["cache_misses"]
    return 100.0 * job["compile"]["cache_hits"] / asked if asked else None

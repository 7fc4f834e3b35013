"""Seconds the warm-up job spent loading executables from the persistent
compile cache (`process.first_job.compile.cache_load_s`)."""

from benchmark import setup_ledger


def read(run: dict):
    return setup_ledger.compiled(run, "cache_load_s")

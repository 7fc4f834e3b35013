"""Seconds of `stage:ingest_or_cache`, whole, in a job that sketches:
`ingest/sketch` (the main thread's wall from the pool's spawn, which is
`ingest/pool_start` inside it, to the last result and the last
`ingest/shard_flush`) and `ingest/cache_save`. `load_sketches_s` reads the same
stage's self seconds, which in such a job is only what these spans leave; in a
job on a planted cache the stage has no spans inside and the two would agree.
None where the job sketched nothing. Median over the window's jobs."""

from benchmark import spans


def read(run: dict):
    if spans.seconds(run, "ingest/sketch") is None:
        return None
    return spans.seconds(run, "stage:ingest_or_cache")

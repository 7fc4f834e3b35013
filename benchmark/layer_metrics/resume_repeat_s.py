"""Seconds a stopped job spends in phases whose work the stores do not hold,
so that every attempt pays them again where an undisturbed job pays them
once: reading the sketch cache back (`stage:ingest_or_cache`), the primary's
pack and its put to the device, the assembly of the edges and the linkage,
the pair table built and written (`mdb_build`, `tables_io`: in an attempt that
stops, the stored Bdb read and the Mdb's write) and its columns kept for the
evaluation (`evaluate/columns`). Self seconds of those phases over every
attempt of a job but the last, which pays each once as an undisturbed job
does. `primary/publish` is left out: it holds the store's key (repeated) and
the shards' writes (not repeated) in one span. Median over the window's jobs;
None where a job has no `attempts` (every kind but `resume_jobs`)."""

import statistics

REPEATED = ("stage:ingest_or_cache", "primary/pack", "primary/put", "primary/assemble",
            "primary/linkage", "mdb_build", "tables_io", "evaluate/columns")


def read(run: dict):
    per_job = []
    for job in run.get("jobs", []):
        attempts = job.get("attempts")
        if not attempts or len(attempts) < 2:
            continue
        total, found = 0.0, False
        for attempt in attempts[:-1]:
            phases = (attempt.get("record") or {}).get("phases") or {}
            for name in REPEATED:
                if name in phases:
                    total += phases[name]["self_seconds"]
                    found = True
        if found:
            per_job.append(total)
    return statistics.median(per_job) if per_job else None

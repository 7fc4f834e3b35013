"""Share of the ingest pool's capacity that sketched: the workers' own busy
seconds (the record's `ingest` counter) over workers x the wall of
`ingest/sketch`, the main thread's span from the pool's spawn to its last
result and the last shard flush. What is missing from 100% is the spawn, the
stragglers of heavy-tailed genome sizes, and results waiting their turn. Over
the window's jobs."""


def read(run: dict):
    busy = capacity = 0.0
    for job in run.get("jobs", []):
        ingest = job["record"].get("ingest") or {}
        span = (job["record"].get("phases") or {}).get("ingest/sketch")
        if ingest.get("workers") and span:
            busy += ingest["busy_seconds"]
            capacity += ingest["workers"] * span["seconds"]
    return 100.0 * busy / capacity if capacity > 0 else None

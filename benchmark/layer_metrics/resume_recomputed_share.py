"""Share of an undisturbed job's comparisons that a stopped job made more
than once. Two stages, each over its own pairs, because a Mash tile's pair and
a containment pair are not one unit: the primary's pairs (`stages.
primary_compare.pairs`, the real pairs of the tiles an attempt dispatched) and
the secondary's (the pairs inside the clusters of every one-shot call,
`secondary_calls[].useful_pairs`, booked when the call is made, and the pairs
the greedy engine consumed, `secondary_greedy_calls[].compared_pairs`), summed
over a job's attempts, less the undisturbed warm-up job's own, over the
latter; the two shares' mean. The design of PR 47 reads 0 for the primary (a
published stripe is never dispatched again) and, for the secondary, the rest
of the one batched call in flight at the stop. Median over the window's jobs;
None for a program without the counters and where the run kept no undisturbed
job's record."""

import statistics


def stage_pairs(record: dict) -> tuple[int, int]:
    """(primary pairs, secondary pairs) a record says were compared."""
    primary = (record.get("stages") or {}).get("primary_compare", {}).get("pairs", 0)
    secondary = sum(c["useful_pairs"] for c in record.get("secondary_calls") or [])
    secondary += sum(c["compared_pairs"] for c in record.get("secondary_greedy_calls") or [])
    return int(primary), int(secondary)


def read(run: dict):
    plain = run.get("undisturbed")
    if not plain or "resume" not in plain:
        return None
    once = stage_pairs(plain)
    if not all(once):
        return None
    per_job = []
    for job in run.get("jobs", []):
        if not job.get("attempts"):
            continue
        done = stage_pairs(job["record"])
        per_job.append(100.0 * sum(max(0, d - o) / o for d, o in zip(done, once)) / len(once))
    if not per_job:
        return None
    done = stage_pairs(run["jobs"][-1]["record"])
    print(f"layer: the last job compared {done[0]} primary pairs (an undisturbed job {once[0]}) and "
          f"{done[1]} secondary pairs (an undisturbed job {once[1]})", flush=True)
    return statistics.median(per_job)

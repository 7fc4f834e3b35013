"""Representatives an engine cluster holds when its scan ends: the mean of
`reps` over the record's `secondary_greedy_calls`, over the window's jobs. The
engine's cost is members x representatives, and its representative tile holds
512 rows."""


def read(run: dict):
    reps = [call["reps"] for job in run.get("jobs", [])
            for call in job["record"].get("secondary_greedy_calls") or []]
    return sum(reps) / len(reps) if reps else None

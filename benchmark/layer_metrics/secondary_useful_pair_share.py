"""Share of the pairs the one-shot secondary calls compute that are read:
a call computes every pair of its padded rows and only the pairs inside a
cluster are used. From the record's `secondary_calls` (one entry per program
shape: calls, rows_pad, useful_pairs), summed over the window's jobs."""


def read(run: dict):
    useful = computed = 0
    for job in run.get("jobs", []):
        for call in job["record"].get("secondary_calls") or []:
            useful += call["useful_pairs"]
            computed += call["calls"] * call["rows_pad"] * (call["rows_pad"] - 1) // 2
    return 100.0 * useful / computed if computed else None

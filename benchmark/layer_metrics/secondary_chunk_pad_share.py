"""Share of the id slots the vocabulary-chunked calls ship that are padding:
every chunk of every row is as wide as the fullest one, rounded up to a power
of two. From the record's `secondary_chunked_calls` (one entry per program
shape: `hashes` real ids in `id_slots` slots), summed over the window's jobs."""


def read(run: dict):
    hashes = slots = 0
    for job in run.get("jobs", []):
        for call in job["record"].get("secondary_chunked_calls") or []:
            hashes += call["hashes"]
            slots += call["id_slots"]
    return 100.0 * (1.0 - hashes / slots) if slots else None

"""How evenly the streaming primary dealt its tiles over the local devices:
the least `tiles` of a slot over the most, from the record's
`primary_stream_slots.by_slot`, each slot summed over the window's jobs. 100%
is an even deal; a chip the walk never reached reads 0. A record without the
counter gives None."""


def read(run: dict):
    per_job = [[did["tiles"] for did in job["record"]["primary_stream_slots"]["by_slot"]]
               for job in run.get("jobs", []) if job["record"].get("primary_stream_slots")]
    by_slot = [sum(tiles) for tiles in zip(*per_job)]
    return 100.0 * min(by_slot) / max(by_slot) if by_slot and max(by_slot) else None

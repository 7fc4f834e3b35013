"""Share of the device turns of the streaming primary that hold no tile: a
stripe's tiles are all finalized before the next stripe's first is dispatched,
so a stripe of t tiles takes ceil(t / slots) turns of the host's chips and
the last turn is seldom full. 1 - tiles / (slots x turns) of the record's
`primary_stream_slots`, summed over the window's jobs. A record without the
counter (a program before it, or a job that streamed nothing) gives None."""


def read(run: dict):
    tiles = room = 0
    for job in run.get("jobs", []):
        slots = job["record"].get("primary_stream_slots") or {}
        tiles += slots.get("tiles", 0)
        room += slots.get("slots", 0) * slots.get("turns", 0)
    return 100.0 * (1.0 - tiles / room) if room else None

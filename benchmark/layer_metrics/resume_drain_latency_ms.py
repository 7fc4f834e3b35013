"""What a thirty-second notice has to hold: milliseconds from the boundary at
which a stop fired (the program's stamp of the drain request on
time.monotonic()'s clock, `drain.requested_monotonic_s` in the record of the
attempt that left) to the return of ``controller.main`` on the same clock,
with the attempt's record written in between; the larger of a job's stops.
Median over the window's jobs; None for a program whose record has no `drain`
and for a job that was never stopped."""

import statistics


def read(run: dict):
    per_job = []
    for job in run.get("jobs", []):
        took = [attempt["returned_s"] - attempt["record"]["drain"]["requested_monotonic_s"]
                for attempt in job.get("attempts") or []
                if ((attempt.get("record") or {}).get("drain") or {}).get("requested_monotonic_s")]
        if took:
            per_job.append(1000.0 * max(took))
    return statistics.median(per_job) if per_job else None

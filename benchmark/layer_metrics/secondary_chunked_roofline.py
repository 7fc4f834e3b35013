"""Share of its roofline the vocabulary-chunked secondary reaches in the
traced job: the seconds its calls need at the published peaks
(roofline_chunked.py, shapes from the record's `secondary_chunked_calls`)
over the device seconds of the operations that ran inside the calls'
`drep:secondary/wait` spans (transfer, chunk loop, readback) on the first
device. A one-shot call opens the same span, so a job that made any is not
read: its spans cannot be told apart by name."""

from benchmark import phases, roofline_chunked, tracered

WAIT = phases.TRACE_PREFIX + "secondary/wait"


def read(run: dict):
    trace, peaks = run.get("trace"), run.get("peaks")
    if not trace or not peaks or not run.get("jobs"):
        return None
    record = run["jobs"][0]["record"]  # the traced job is the window's first
    calls = record.get("secondary_chunked_calls")
    if not calls or record.get("secondary_calls"):
        return None
    events = trace["events"]
    planes = sorted(p for p, ev in events["devices"].items() if ev)
    waits = tracered.merge_intervals(
        [(s, s + d) for name, s, d in events["host"] if name == WAIT])
    if not planes or not waits:
        return None
    seconds = sum(d for _, s, d in events["devices"][planes[0]]
                  if any(lo <= s < hi for lo, hi in waits)) / 1e9
    if seconds <= 0:
        return None
    least, bound = roofline_chunked.chunked_least_seconds(calls, peaks)
    print(f"layer: chunked secondary needs {least:.5f} s at the {bound} peak, its device "
          f"operations took {seconds:.4f} s", flush=True)
    return 100.0 * least / seconds

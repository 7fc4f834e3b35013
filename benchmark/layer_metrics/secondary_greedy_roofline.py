"""Share of its roofline the greedy engine's device work reaches in the
traced job: the least seconds its clusters' comparisons need at the published
peaks (roofline_greedy.py: from `rows`, `extent`, `hashes` and
`compared_pairs` of the record's `secondary_greedy_calls`, never from the
tiles or the chunk plan) over the device seconds of the operations that start
inside the engine's `drep:secondary/greedy_wait` spans (transfer, tile loop,
self comparison, readbacks) on the first device. The one-shot calls of the
same job open `drep:secondary/wait`, a name of its own, so they are not
counted."""

from benchmark import phases, roofline_greedy, tracered

WAIT = phases.TRACE_PREFIX + "secondary/greedy_wait"


def read(run: dict):
    trace, peaks = run.get("trace"), run.get("peaks")
    if not trace or not peaks or not run.get("jobs"):
        return None
    calls = run["jobs"][0]["record"].get("secondary_greedy_calls")  # the traced job is the first
    if not calls:
        return None
    events = trace["events"]
    planes = sorted(p for p, ev in events["devices"].items() if ev)
    waits = tracered.merge_intervals(
        [(s, s + d) for name, s, d in events["host"] if name == WAIT])
    if not planes or not waits:
        return None
    seconds = sum(d for _, s, d in events["devices"][planes[0]]
                  if any(lo <= s < hi for lo, hi in waits)) / 1e9
    least, bound = roofline_greedy.greedy_least_seconds(calls, peaks)
    if seconds <= 0 or least <= 0:
        return None
    print(f"layer: greedy engine needs {least:.5f} s at the {bound} peak, its device "
          f"operations took {seconds:.4f} s", flush=True)
    return 100.0 * least / seconds

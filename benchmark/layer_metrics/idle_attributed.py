"""Share of the first device's idle time inside the traced job that lies
inside a named phase: a `drep:<span>` event of the profiler's host plane
other than `drep:job` and the spans that only hold other spans. The job is
the `drep:job` event; idle is the job less the union of the device's
operations."""

from benchmark import phases, tracered


def _gaps(lo: float, hi: float, busy: list) -> list:
    """The parts of [lo, hi] that no interval of the sorted, disjoint `busy` covers."""
    out, at = [], lo
    for s, e in busy:
        if e <= at or s >= hi:
            continue
        if s > at:
            out.append((at, s))
        at = e
    if at < hi:
        out.append((at, hi))
    return out


def _overlap(a: list, b: list) -> float:
    """Length covered by both of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    events = trace["events"]
    spans = [(name[len(phases.TRACE_PREFIX):], s, s + d) for name, s, d in events["host"]
             if name.startswith(phases.TRACE_PREFIX)]
    jobs = [(s, e) for name, s, e in spans if name == "job"]
    planes = sorted(p for p, ev in events["devices"].items() if ev)
    if not jobs or not planes:
        return None
    lo, hi = max(jobs, key=lambda se: se[1] - se[0])
    busy = tracered.merge_intervals(
        [(s, s + d) for _, s, d in events["devices"][planes[0]] if d > 0])
    idle = _gaps(lo, hi, busy)
    idle_ns = sum(e - s for s, e in idle)
    if idle_ns <= 0:
        return None
    named = tracered.merge_intervals(
        [(s, e) for name, s, e in spans if name not in phases.CONTAINERS])
    return 100.0 * _overlap(named, idle) / idle_ns

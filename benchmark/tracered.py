"""From a profiler trace (.xplane.pb) to numbers: device busy time, kernel
time, the share of a window in which only a collective runs, the operations
that took most time, and the longest idle gaps with what the host was doing.

Two steps, so that the arithmetic can be tested on a small recorded trace:
``load_xplane`` reads the profiler's file with ``jax.profiler.ProfileData``
into plain lists of (name, start_ns, duration_ns); everything else is a pure
function of those lists. What a TPU v5e trace looks like (planes, lines, the
names XLA and Mosaic give the kernels) is written down in PERF.md, section 3,
from a trace read by hand.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"  # one event per HLO operation as it ran on the device
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(r"collective-permute|ppermute|all-reduce|all-gather|all-to-all|reduce-scatter")


def short_name(name: str) -> str:
    """A device operation's own name. On the v5e an event of the ops line is
    named by its whole HLO text ("%fusion.1 = s32[...] fusion(...)"); the part
    before " = ", without the "%", is the operation."""
    return name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load_xplane(path: str, rehearse: bool = False) -> dict:
    """{"devices": {plane name: [(op name, start_ns, dur_ns), ...]},
    "host": [(event name, start_ns, dur_ns), ...]}. In a rehearsal on the CPU
    there is no device plane, and XLA's CPU executor threads stand in for one
    so that the code after this runs; nothing read from them is a device number."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (short_name(e.name), float(e.start_ns), float(e.duration_ns))
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if rehearse and line.name.startswith("tf_XLAPjRtCpuClient"):
                    devices.setdefault("/rehearsal:CPU", []).extend(
                        (e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events)
                    continue
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events if e.duration_ns > 0)
    return {"devices": devices, "host": host}


# ---- pure arithmetic ---------------------------------------------------------


def merge_intervals(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, disjoint (start, end) covering the same points."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _covered(intervals) -> float:
    return sum(e - s for s, e in merge_intervals(intervals))


def _spans(events, keep=None):
    return [(s, s + d) for name, s, d in events if d > 0 and (keep is None or keep(name))]


def busy_seconds(trace: dict) -> float:
    """Seconds in which an operation ran on the device: the union of the
    op intervals of each device, averaged over the devices that ran any."""
    per = [_covered(_spans(ev)) for ev in trace["devices"].values() if ev]
    return sum(per) / len(per) / 1e9 if per else 0.0


def op_seconds(trace: dict, pattern: str) -> float:
    """Device seconds of the operations whose name matches, summed over
    events and devices."""
    rx = re.compile(pattern)
    return sum(d for ev in trace["devices"].values() for name, _, d in ev if rx.search(name)) / 1e9


def exposed_collective_seconds(trace: dict) -> float:
    """Seconds in which a collective runs on a device and no other
    operation does, on the device where that is longest."""
    worst = 0.0
    for ev in trace["devices"].values():
        coll = merge_intervals(_spans(ev, lambda n: bool(COLLECTIVE.search(n))))
        comp = merge_intervals(_spans(ev, lambda n: not COLLECTIVE.search(n)))
        hidden = 0.0
        for cs, ce in coll:
            for ps, pe in comp:
                hidden += max(0.0, min(ce, pe) - max(cs, ps))
        worst = max(worst, (sum(e - s for s, e in coll) - hidden) / 1e9)
    return worst


def top_ops(trace: dict, n: int = 10) -> list[list]:
    """[[name, seconds], ...]: the operations with most device time, summed
    over devices."""
    total: dict[str, float] = {}
    for ev in trace["devices"].values():
        for name, _, d in ev:
            total[name] = total.get(name, 0.0) + d
    return [[k, total[k] / 1e9] for k in sorted(total, key=total.get, reverse=True)[:n]]


def idle_gaps(trace: dict, n: int = 5) -> list[list]:
    """[[label, seconds], ...]: the longest stretches with no operation on the
    first device, the one before its first operation and the one after its
    last among them (the trace begins and ends with its first and last event,
    on the host or the device). Each is labelled with the shortest host event
    that covers most of it ("host:<event>"), else "host:unattributed"."""
    planes = sorted(p for p, ev in trace["devices"].items() if ev)
    if not planes:
        return []
    busy = merge_intervals(_spans(trace["devices"][planes[0]]))
    everything = [e for ev in trace["devices"].values() for e in ev] + trace["host"]
    lo = min(s for _, s, _ in everything)
    hi = max(s + d for _, s, d in everything)
    edges = [(lo, lo)] + busy + [(hi, hi)]
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(edges, edges[1:]) if b[0] > a[1]),
                  reverse=True)[:n]
    out = []
    for length, gs, ge in gaps:
        best, best_len = "unattributed", 0.0
        for name, s, d in trace["host"]:
            cover = min(ge, s + d) - max(gs, s)
            if cover > 0.5 * length and (best_len == 0.0 or d < best_len):
                best, best_len = name, d
        out.append([f"host:{best}", length / 1e9])
    return out


def reduce_trace(trace: dict, window_s: float) -> dict:
    """What a traced run reports: busy_s, window_s and the breakdown."""
    return {
        "busy_s": busy_seconds(trace),
        "window_s": window_s,
        "breakdown": {"device_ops": top_ops(trace, 10), "idle_gaps": idle_gaps(trace, 5)},
    }

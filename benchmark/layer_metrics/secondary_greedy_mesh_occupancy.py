"""What the greedy engine keeps of the host's chips while it compares: the
device-busy seconds of every chip inside the traced job's
`drep:secondary/greedy_wait` and `drep:secondary/greedy_put` spans (the union
of a chip's operation intervals, cut to the spans), summed over the chips,
over chips x the seconds those spans cover. What is missing is the host
between the calls: the puts, the dispatch of one program a chunk, the gather
and the readback of every tile, and the chips a block's padding leaves with
nothing to do. The chips are the run's (`device.count`): a chip the trace has
no operation of counts as idle. None without a trace, without the spans, or
for a program whose record does not say how many devices served
(`mesh_devices`)."""

from benchmark import phases, tracered
from benchmark.layer_metrics.idle_attributed import _overlap  # two sorted lists of disjoint intervals

SPANS = tuple(phases.TRACE_PREFIX + name for name in ("secondary/greedy_wait", "secondary/greedy_put"))


def read(run: dict):
    trace = run.get("trace")
    if not trace or not run.get("jobs"):
        return None
    calls = run["jobs"][0]["record"].get("secondary_greedy_calls") or []  # the traced job is the first
    if not any("mesh_devices" in c for c in calls):
        return None
    events = trace["events"]
    engine = tracered.merge_intervals([(s, s + d) for name, s, d in events["host"] if name in SPANS])
    covered = sum(e - s for s, e in engine)
    chips = int(run["device"]["count"])
    if covered <= 0 or chips <= 0:
        return None
    busy = sum(_overlap(tracered.merge_intervals([(s, s + d) for _, s, d in ops if d > 0]), engine)
               for ops in events["devices"].values())
    return 100.0 * busy / (chips * covered)

"""What the streaming primary keeps of the host's chips while it streams:
the device-busy seconds of every chip inside the traced job's `drep:stripe`
spans (the union of a chip's operation intervals, cut to the spans), summed
over the chips, over chips x the seconds those spans cover. What is missing
is the stripe barrier (a stripe's tiles are all finalized, its edges joined
and its shard published before the next stripe's first tile is dispatched),
the per-tile syncs and the turns that are not full. The chips are the run's
(`device.count`): a chip the trace has no operation of counts as idle. A
trace without the span (a program before it, or a job that streamed nothing)
gives None."""

from benchmark import phases, tracered
from benchmark.layer_metrics.idle_attributed import _overlap  # two sorted lists of disjoint intervals

SPAN = phases.TRACE_PREFIX + "stripe"


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None
    events = trace["events"]
    stripes = tracered.merge_intervals([(s, s + d) for name, s, d in events["host"] if name == SPAN])
    covered = sum(e - s for s, e in stripes)
    chips = int(run["device"]["count"])
    if covered <= 0 or chips <= 0:
        return None
    busy = sum(_overlap(tracered.merge_intervals([(s, s + d) for _, s, d in ops if d > 0]), stripes)
               for ops in events["devices"].values())
    return 100.0 * busy / (chips * covered)

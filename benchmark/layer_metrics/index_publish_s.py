"""Seconds of the `index/publish` span in a job's own record: the batch's
sketch shard (head and parts), its edge shard, the whole state and the
manifest, each an atomic checked publish (`index/publish_sketch|edges|state|
manifest` inside). Median over the window's jobs; None where the program has
no such span."""
from benchmark import spans


def read(run: dict):
    return spans.seconds(run, "index/publish")

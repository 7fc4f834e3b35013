"""Seconds of the `index/load` span in a job's own record: the manifest and
every shard of the stored index read back into memory and checked (a shard's
parts are read in place and checksummed as they are placed, so the check has
no span of its own). Median over the window's jobs; None where the program
has no such span."""
from benchmark import spans


def read(run: dict):
    return spans.seconds(run, "index/load")

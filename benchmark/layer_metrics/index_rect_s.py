"""Seconds of the `index/rect_compare` span in a job's own record: the batch
against everything, the union's pack and the streaming tiles of the last block
rows (`primary/pack`, `primary/put`, `primary/dispatch`, `primary/wait` inside
it). Median over the window's jobs; None where the program has no such span."""
from benchmark import spans


def read(run: dict):
    return spans.seconds(run, "index/rect_compare")

"""Seconds of `stage:evaluate`, whole: `evaluate/tables` (the tables read
back, Widb written) and `evaluate/warnings` (the winner and coverage
warnings, `warnings.txt`). Every job has the stage; the metric is listed for
the `dereplicate` cell, where the winners are real. Median over the window's
jobs."""

from benchmark import spans


def read(run: dict):
    return spans.seconds(run, "stage:evaluate")

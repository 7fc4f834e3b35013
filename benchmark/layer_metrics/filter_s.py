"""Seconds of `stage:filter`, whole: `filter/fasta_stats` (the serial read of
every FASTA in the main process for length, N50 and contigs),
`filter/quality` and the stage's table writes. Only a `dereplicate` job has
it. Median over the window's jobs."""

from benchmark import spans


def read(run: dict):
    return spans.seconds(run, "stage:filter")

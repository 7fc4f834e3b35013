"""Seconds of `stage:choose`, whole: `choose/tables` (Cdb, Ndb and the
quality tables read back, Sdb and Wdb written), `choose/score` with
`choose/centrality` inside it, `choose/copy` (the winners' files copied to
`dereplicated_genomes/`). Only a `dereplicate` job has it. Median over the
window's jobs."""

from benchmark import spans


def read(run: dict):
    return spans.seconds(run, "stage:choose")

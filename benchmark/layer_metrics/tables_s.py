"""Seconds spent building and writing tables: `mdb_build` (the pair table
from the distances), `tables_io` (Bdb, Mdb, genomeInformation, the stored
arguments) and `stage:assembly_io` (Ndb, Cdb, the clustering pickle). Median
over the window's jobs."""

from benchmark import phases


def read(run: dict):
    return phases.self_seconds(run, ("mdb_build", "tables_io", "stage:assembly_io"))

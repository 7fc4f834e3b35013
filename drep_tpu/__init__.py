"""drep_tpu — a TPU-native genome dereplication and comparison framework.

A from-scratch rebuild of the capabilities of dRep (SilasK/drep fork of
MrOlm/drep; see SURVEY.md): quality-filter genomes, form coarse primary
clusters from an all-vs-all MinHash (Mash) distance matrix, refine with
pairwise ANI into secondary clusters, and pick one winner genome per
secondary cluster by a quality score.

The execution model is TPU-first rather than a port of the reference's
subprocess orchestration (reference: drep/d_cluster/external.py shells out
to `mash`/`fastANI`; unverifiable against the empty reference mount — see
SURVEY.md §0):

- host ingest: FASTA -> canonical k-mer 64-bit hashes -> packed sketches
- device compute: vmapped / Pallas all-pairs kernels over ``jax.sharding.Mesh``
- tiny host post-processing into the canonical pandas tables
  (Bdb/Mdb/Ndb/Cdb/Sdb/Wdb) persisted through :class:`WorkDirectory`.
"""

import time as _time

__version__ = "0.5.0"

# the package's first import on the process's clock: the record's
# `process.imported_at_s` (utils/profiling.py::ProcessLedger)
_IMPORTED_AT = _time.perf_counter()


def __getattr__(name):  # PEP 562 — keep the package import lean: ingest
    # pool workers import drep_tpu.* and must not pay for pandas/workdir
    # (measured 2.7 s cold per worker vs ~0.7 s without)
    if name == "WorkDirectory":
        from drep_tpu.workdir import WorkDirectory

        return WorkDirectory
    if name == "setup_logger":
        from drep_tpu.utils.logger import setup_logger

        return setup_logger
    raise AttributeError(f"module 'drep_tpu' has no attribute {name!r}")

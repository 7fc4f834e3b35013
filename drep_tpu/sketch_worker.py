"""The per-genome sketch job — deliberately a LEAN module.

Ingest pool workers (ingest.py::sketch_genomes) import the module that
defines their job function; keeping this one's import chain to numpy +
the native bindings + the k-mer kernels (~0.7 s cold vs ~2.7 s for
drep_tpu.ingest with its pandas dependency) is what makes a process pool
pay off at small batch counts — worker startup was measured to exceed the
sketching itself at <100 genomes otherwise.
"""

from __future__ import annotations

import os
import time

import numpy as np

from drep_tpu.ops import kmers
from drep_tpu.utils.fasta import fasta_stats, n50, read_fasta_contigs


def sketch_one(args) -> tuple[str, dict]:
    """(name, path, k, sketch_size, scale, hash_name) -> (name, result
    dict with length/N50/contigs/n_kmers/bottom/scaled, and what the
    record's `ingest` counter sums: the file's bytes, the valid k-mers
    hashed and the seconds this call took, on either path).

    A job of (name, path) alone is read for its stats: the same parse, the
    same length/N50/contigs, no k-mer hashed (`valid_kmers` and `n_kmers`
    0) and no `bottom` or `scaled` in the result. `dereplicate` sends such
    a job for a genome its quality table already drops (filter.py)."""
    name, path = args[:2]
    t0 = time.perf_counter()
    res = _sketch(*args[1:]) if len(args) > 2 else _stats(path)
    res["file_bytes"] = os.path.getsize(path)
    res["seconds"] = time.perf_counter() - t0
    return name, res


def _stats(path) -> dict:
    from drep_tpu.native import fasta_stats_native

    stats = fasta_stats_native(path)
    if stats is None:
        st = fasta_stats(path)
        stats = {"length": st.length, "N50": st.N50, "contigs": st.contigs}
    return {**stats, "n_kmers": 0, "valid_kmers": 0}


def _sketch(path, k, sketch_size, scale, hash_name) -> dict:
    from drep_tpu.native import sketch_fasta_native

    native = sketch_fasta_native(path, k, sketch_size, scale, hash_name)
    if native is not None:
        return native

    contigs = read_fasta_contigs(path)
    lengths = np.array([len(c) for c in contigs], dtype=np.int64)
    raw = np.concatenate(
        [kmers.hash_kmers(kmers.packed_kmers(c, k), k, hash_name) for c in contigs]
        or [np.empty(0, np.uint64)]
    )
    bottom, scaled, n_kmers = kmers.sketches_from_raw(raw, sketch_size, scale)
    return {
        "length": int(lengths.sum()) if len(lengths) else 0,
        "N50": n50(lengths),
        "contigs": len(contigs),
        "n_kmers": n_kmers,
        "valid_kmers": int(raw.size),
        "bottom": bottom,
        "scaled": scaled,
    }

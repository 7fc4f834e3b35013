"""Chunked (multi-round) primary clustering for very large genome sets.

Reference parity: `--multiround_primary_clustering` / `--primary_chunksize`
(drep/d_cluster/compare_utils.py::multiround_primary_clustering, SURVEY.md
§2; reference mount empty). Avoids materializing the full N^2 Mash table:

round 1: split genomes into chunks, all-vs-all Mash + clustering within
         each chunk; elect one representative (most k-mers) per
         within-chunk cluster.
round 2: all-vs-all Mash over the representatives only; merge clusters
         whose representatives co-cluster; every genome inherits its
         representative's final cluster.

This is an approximation (as in the reference): genomes whose similarity
straddles two chunks only merge if their representatives do.
"""

from __future__ import annotations

from typing import Any

import numpy as np
import pandas as pd

from drep_tpu.ingest import GenomeSketches
from drep_tpu.ops.linkage import cluster_hierarchical
from drep_tpu.utils.logger import get_logger


def _cluster_chunk(
    gs: GenomeSketches,
    idx: list[int],
    cutoff: float,
    method: str,
    mesh_shape: int | None,
    processes: int = 1,
) -> np.ndarray:
    from drep_tpu.cluster.engines import mash_distance_matrix, pack_primary

    packed = pack_primary(
        [gs.bottom[i] for i in idx], [gs.names[i] for i in idx], gs.sketch_size, processes
    )
    dist = mash_distance_matrix(packed, gs.k, mesh_shape=mesh_shape)
    labels, _ = cluster_hierarchical(dist, cutoff, method=method)
    return labels


def multiround_primary_clustering(
    gs: GenomeSketches, bdb: pd.DataFrame, kw: dict[str, Any]
) -> tuple[np.ndarray, int]:
    """Returns (labels 1..C, pairs actually compared across both rounds)."""
    logger = get_logger()
    n = len(gs.names)
    chunk = int(kw["primary_chunksize"])
    cutoff = 1.0 - kw["P_ani"]
    method = kw["clusterAlg"]
    mesh_shape = kw.get("mesh_shape")
    processes = kw.get("processes", 1)
    nk = gs.gdb["n_kmers"].to_numpy()

    # round 1: within-chunk clustering, elect representatives
    rep_of_genome = np.zeros(n, dtype=np.int64)  # genome -> its representative index
    reps: list[int] = []
    pairs_compared = 0
    for c0 in range(0, n, chunk):
        idx = list(range(c0, min(c0 + chunk, n)))
        pairs_compared += len(idx) * (len(idx) - 1) // 2
        labels = _cluster_chunk(gs, idx, cutoff, method, mesh_shape, processes)
        # one grouping pass — a per-label membership scan is
        # O(clusters * chunk), ~170M Python iterations at the 100k scale
        groups: dict[int, list[int]] = {}
        for t, lab in enumerate(labels):
            groups.setdefault(int(lab), []).append(idx[t])
        for lab in sorted(groups):
            members = groups[lab]
            rep = max(members, key=lambda i: int(nk[i]))
            reps.append(rep)
            for i in members:
                rep_of_genome[i] = rep
    logger.info("multiround: %d chunks -> %d representatives", -(-n // chunk), len(reps))

    # round 2: cluster the representatives
    pairs_compared += len(reps) * (len(reps) - 1) // 2
    rep_labels = _cluster_chunk(gs, reps, cutoff, method, mesh_shape, processes)
    label_of_rep = {rep: int(rep_labels[t]) for t, rep in enumerate(reps)}

    raw = np.array([label_of_rep[int(rep_of_genome[i])] for i in range(n)], dtype=np.int64)
    # renumber by first appearance for determinism
    out = np.zeros(n, dtype=np.int64)
    seen: dict[int, int] = {}
    for i, lab in enumerate(raw):
        if int(lab) not in seen:
            seen[int(lab)] = len(seen) + 1
        out[i] = seen[int(lab)]
    return out, pairs_compared

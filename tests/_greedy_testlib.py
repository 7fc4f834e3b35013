"""Clusters for the greedy engine's representative tile (ISSUE 55), built so
that the representatives each block meets are known without running anything,
and the tile rule the engine had before: whole tiles of 512 rows, one at the
least, kept here so the tests can hold the sized tiles to the same answers."""

import numpy as np
import pandas as pd

from drep_tpu.cluster.greedy import greedy_secondary_cluster
from drep_tpu.ingest import GenomeSketches
from drep_tpu.utils.profiling import counters


def tile_cluster(founders: list[int], block: int = 128, last: int = 8) -> GenomeSketches:
    """One primary cluster of `len(founders)` blocks of `block` genomes (the
    last one of `last`), visited in the order written: of each block the
    first `founders[b]` genomes are strangers (a fifth of their hashes in
    common with anyone: each founds a group) and the others near copies of
    the cluster's first genome (they join its group). So block b meets
    `sum(founders[:b])` representatives."""
    assert founders[0] >= 1 and all(0 <= f <= block for f in founders)
    rng = np.random.default_rng(55)
    core = rng.choice(np.uint64(1) << np.uint64(40), size=60, replace=False).astype(np.uint64)

    def stranger():
        return np.unique(np.concatenate([core, rng.integers(1 << 41, 1 << 62, size=240, dtype=np.uint64)]))

    scaled: list[np.ndarray] = []
    for b, n_founders in enumerate(founders):
        rows = last if b == len(founders) - 1 and len(founders) > 1 else block
        for t in range(max(rows, n_founders)):
            if t < n_founders:
                scaled.append(stranger())
            else:
                # three hashes of the first genome's own replaced
                keep = np.delete(scaled[0], rng.choice(np.arange(60, 300), size=3, replace=False))
                scaled.append(np.unique(np.concatenate(
                    [keep, rng.integers(1 << 41, 1 << 62, size=3, dtype=np.uint64)])))
    names = [f"g{i}" for i in range(len(scaled))]
    gdb = pd.DataFrame({"genome": names, "n_kmers": range(100_000, 100_000 - len(names), -1)})
    return GenomeSketches(names=names, gdb=gdb, bottom=[s[:100] for s in scaled], scaled=scaled,
                          k=21, sketch_size=100, scale=200)


def fixed_tiles(n_reps: int, base_block: int) -> list[int]:
    """`greedy._rep_tile_rows` as the engine tiled before ISSUE 55."""
    rep_tile = 4 * base_block
    return [rep_tile] * max(-(-n_reps // rep_tile), 1)


def sized_tiles(n_reps: int) -> list[int]:
    """What ISSUE 55's rule gives at blocks of 128, spelled out."""
    full, rest = divmod(n_reps, 512)
    trailing = [] if rest == 0 else [128] if rest <= 128 else [256] if rest <= 256 else [512]
    return [512] * full + trailing


def run_engine(gs: GenomeSketches, mesh_shape: int = 1):
    """(the Ndb as a frame, the labels, the job's record) of one engine call
    on the whole of `gs`, counters reset before it."""
    counters.reset()
    ndb, labels = greedy_secondary_cluster(
        gs, None, list(range(len(gs.names))), pc=1,
        kw={"S_ani": 0.95, "cov_thresh": 0.1, "mesh_shape": mesh_shape})
    return ndb.frame(), labels, counters.report(device=False)


def assert_same_answers(ndb, labels, other_ndb, other_labels) -> None:
    """Labels and every Ndb column equal bit for bit."""
    np.testing.assert_array_equal(labels, other_labels)
    assert list(ndb.columns) == list(other_ndb.columns)
    for col in ndb.columns:
        np.testing.assert_array_equal(ndb[col].to_numpy(), other_ndb[col].to_numpy(), err_msg=col)

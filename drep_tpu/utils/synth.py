"""Shared synthetic sketch planting for chip_smoke, chaos cells, and tests.

One recipe for the "group-pool" packed sketches that the LSH pruning
work measures itself against: members of a group draw their sketch ids
from a common pool (small Mash distance inside the group, ~none across),
and `contiguous=True` lays group members out adjacently in index order —
the realistic post-sort layout where candidate pruning actually skips
tiles (interleaved members occupy every tile, the worst case). Kept in
ONE place so chip_smoke.py, the chaos matrix (tools/chaos_matrix.py
--prune), and the test suites cannot drift onto
subtly different data while claiming to measure the same property.

(The pre-existing per-suite planters — tests/_chaos_worker.py's
kill-oracle data, tests/test_chaos.py, chaos_matrix._packed — are
deliberately NOT rebased onto this: their byte-exact rng streams anchor
recorded oracles.)
"""

from __future__ import annotations

import numpy as np

# importing this module must not import jax: chip_smoke.py's parent plants
# its workdirs with it while its children hold the chip (ops.minhash, which
# pulls jax in, is imported inside the one function that needs it)


def planted_group_sketches(
    n: int = 256,
    s: int = 64,
    groups: int = 16,
    seed: int = 0,
    contiguous: bool = True,
    id_space: int = 2**20,
) -> "PackedSketches":
    """Group-pool packed sketches: `n` genomes over `groups` pools of
    `2*s` ids drawn from `id_space`, each row an `s`-subset of its
    group's pool. Deterministic per seed."""
    from drep_tpu.ops.minhash import PAD_ID, PackedSketches

    rng = np.random.default_rng(seed)
    ids = np.full((n, s), PAD_ID, np.int32)
    counts = np.full(n, s, np.int32)
    pools = [
        np.sort(rng.choice(id_space, size=s * 2, replace=False).astype(np.int32))
        for _ in range(groups)
    ]
    for i in range(n):
        g = (i * groups // n) if contiguous else (i % groups)
        ids[i] = np.sort(rng.choice(pools[g], size=s, replace=False))
    return PackedSketches(ids=ids, counts=counts, names=[f"g{i}" for i in range(n)])


def plant_genome_sketches(n: int, rng: np.random.Generator, s_scaled: int = 1200):
    """Synthetic GenomeSketches with planted cluster structure, and the
    planted partition: cluster members share ~90% of bottom-sketch hashes
    (well inside 1-P_ani) and ~97% of scaled-sketch hashes (ANI ~ 0.9985 >
    S_ani), clusters share nothing — so at default thresholds every planted
    cluster is exactly one primary AND one secondary cluster. Returns
    ``(GenomeSketches, labels)``, labels the planted cluster per genome.

    `s_scaled` sets the scaled-sketch depth: 1200 is the budget-friendly
    toy width; 20_000 is the PRODUCTION depth (4 Mb genomes at scale=200),
    which packs to width 32768. A workdir is planted from the result with
    ``ingest._save`` + ``store_arguments("sketch", sketch_args_snapshot(...))``
    — the supported resume state, so a run starts at the cluster stage."""
    import pandas as pd

    from drep_tpu.ingest import DEFAULT_SCALE, GenomeSketches
    from drep_tpu.ops.kmers import DEFAULT_K

    s_bottom = 1000
    names, bottoms, scaleds, labels = [], [], [], []
    gi = cluster = 0
    while gi < n:
        size = min(int(rng.geometric(0.35)), 20, n - gi)
        c_bottom = np.unique(rng.integers(0, 2**63, size=int(s_bottom * 1.6), dtype=np.uint64))
        c_scaled = np.unique(rng.integers(0, 2**63, size=int(s_scaled * 1.3), dtype=np.uint64))
        for _ in range(size):
            keep_b = rng.random(len(c_bottom)) < 0.90
            own_b = np.unique(rng.integers(0, 2**63, size=s_bottom // 6, dtype=np.uint64))
            bottoms.append(np.sort(np.concatenate([c_bottom[keep_b], own_b]))[:s_bottom])
            keep_s = rng.random(len(c_scaled)) < 0.97
            own_s = np.unique(rng.integers(0, 2**63, size=s_scaled // 25, dtype=np.uint64))
            scaleds.append(np.sort(np.concatenate([c_scaled[keep_s], own_s])))
            names.append(f"synth_{gi}.fasta")
            labels.append(cluster)
            gi += 1
        cluster += 1
    gdb = pd.DataFrame(
        {
            "genome": names,
            "length": np.full(n, 4_000_000, np.int64),
            "N50": np.full(n, 50_000, np.int64),
            "contigs": np.full(n, 100, np.int64),
            "n_kmers": np.full(n, 3_900_000, np.int64),
        }
    )
    gs = GenomeSketches(
        names=names, gdb=gdb, bottom=bottoms, scaled=scaleds,
        k=DEFAULT_K, sketch_size=s_bottom, scale=DEFAULT_SCALE,
    )
    return gs, np.array(labels, dtype=np.int64)

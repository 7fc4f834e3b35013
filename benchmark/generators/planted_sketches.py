"""Planted sketch sets: a collection given as MinHash sketches, not FASTAs.

A copy of ``drep_tpu/utils/synth.py::plant_genome_sketches`` (the recipe of
chip_smoke.py legs B and C) with the cluster-size law and the sharing inside
a cluster as parameters. The benchmark keeps its own copy so that a later PR
may change the program's and not the yardstick's.

Members of a planted cluster draw their bottom-k hashes from a common pool
(small Mash distance inside the cluster) and their scaled hashes from a
second pool (high containment, so high ANI); clusters share nothing. The
parameters, per configuration file:

    n               genomes
    s_bottom        bottom-k sketch size (1000: never cut)
    s_scaled        scaled-sketch depth (20000 = a 4 Mb genome at --scale 200)
    keep_bottom     share of the cluster's bottom pool a member keeps
    own_bottom      hashes of its own a member adds to its bottom sketch
    keep_scaled, own_scaled_div   the same for the scaled sketch
    cluster_law     {"law": "geometric", "p": 0.35, "cap": 20}  or
                    {"law": "singletons", "share": 0.85, "max": 4}

Importing this module imports neither jax nor the program; ``write_workdir``
uses the program's own writers, because a workdir in the program's format is
the program's input.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np

POOL_BOTTOM = 1.6  # pool sizes, as multiples of the sketch sizes
POOL_SCALED = 1.3


@dataclass
class PlantedSketches:
    names: list[str]
    bottom: list[np.ndarray]  # sorted unique uint64, at most s_bottom each
    scaled: list[np.ndarray]  # sorted unique uint64
    labels: np.ndarray  # planted cluster of each genome
    k: int
    s_bottom: int


def _cluster_size(rng: np.random.Generator, law: dict) -> int:
    if law["law"] == "geometric":
        return min(int(rng.geometric(law["p"])), int(law["cap"]))
    if law["law"] == "singletons":
        if rng.random() < law["share"]:
            return 1
        return int(rng.integers(2, int(law["max"]) + 1))
    raise ValueError(f"unknown cluster_law {law!r}")


def _hashes(rng: np.random.Generator, n: int) -> np.ndarray:
    return np.unique(rng.integers(0, 2**63, size=n, dtype=np.uint64))


def generate(params: dict, seed: int) -> PlantedSketches:
    """The planted collection, a pure function of (params, seed)."""
    rng = np.random.default_rng(seed)
    n, s_b, s_s = int(params["n"]), int(params["s_bottom"]), int(params["s_scaled"])
    keep_b, own_b = float(params["keep_bottom"]), int(params["own_bottom"])
    keep_s, own_s = float(params["keep_scaled"]), max(1, s_s // int(params["own_scaled_div"]))
    names, bottoms, scaleds, labels = [], [], [], []
    gi = cluster = 0
    while gi < n:
        size = min(_cluster_size(rng, params["cluster_law"]), n - gi)
        pool_b = _hashes(rng, int(s_b * POOL_BOTTOM))
        pool_s = _hashes(rng, int(s_s * POOL_SCALED))
        for _ in range(size):
            kept = pool_b[rng.random(len(pool_b)) < keep_b]
            bottoms.append(np.unique(np.concatenate([kept, _hashes(rng, own_b)]))[:s_b])
            kept = pool_s[rng.random(len(pool_s)) < keep_s]
            scaleds.append(np.unique(np.concatenate([kept, _hashes(rng, own_s)])))
            names.append(f"synth_{gi}.fasta")
            labels.append(cluster)
            gi += 1
        cluster += 1
    return PlantedSketches(names, bottoms, scaleds, np.array(labels, np.int64),
                           k=int(params["kmer_size"]), s_bottom=s_b)


def write_workdir(data: PlantedSketches, wd_path: str, params: dict) -> None:
    """A workdir whose Bdb and sketch cache are planted: the program's
    supported resume state, so ``compare <wd>`` (no -g) starts at the cluster
    stage."""
    import pandas as pd

    from drep_tpu.ingest import GenomeSketches, _save, sketch_args_snapshot
    from drep_tpu.workdir import WorkDirectory

    n = len(data.names)
    gdb = pd.DataFrame({
        "genome": data.names,
        "length": np.full(n, int(params["genome_length"]), np.int64),
        "N50": np.full(n, 50_000, np.int64),
        "contigs": np.full(n, 100, np.int64),
        "n_kmers": np.full(n, int(params["genome_length"]) - 100_000, np.int64),
    })
    gs = GenomeSketches(names=data.names, gdb=gdb, bottom=data.bottom, scaled=data.scaled,
                        k=data.k, sketch_size=data.s_bottom, scale=int(params["scale"]))
    wd = WorkDirectory(wd_path)
    wd.store_db(pd.DataFrame({"genome": data.names,
                              "location": [f"/nonexistent/{g}" for g in data.names]}), "Bdb")
    _save(wd, gs)
    wd.store_arguments("sketch", sketch_args_snapshot(
        data.names, data.k, data.s_bottom, int(params["scale"]), params["hash"]))


def prepare(cfg: dict, seed: int, out_dir: str) -> dict:
    """What a batch cell needs: the pristine workdir under `out_dir` and the
    planted data for the reference."""
    data = generate(cfg["data"], seed)
    wd = os.path.join(out_dir, "pristine")
    write_workdir(data, wd, cfg["data"])
    return {"workdir": wd, "data": data}

"""Planted species collection: every public assembly of one genus, given as
MinHash sketches. One primary cluster that holds several species-level
groups, with structure inside a group.

The sketches descend a tree, genus -> species group -> lineage -> strain
clade -> genome. A node's scaled sketch is its parent's, each hash kept with
probability e^k and the lost ones replaced by new hashes of the node's own:
e is the average nucleotide identity along that edge under the k-mer
substitution model the program's estimator assumes, so the ANI of two
genomes is about the product of the edges' e along the path between them,
and a hash is shared by a clade, not only by all or by one. A genome adds up
to `accessory_max` of its size in hashes of its own (genes its clade lacks),
which makes coverage directional. A genome's bottom sketch is the `s_bottom`
smallest hashes of its scaled sketch, as for a real genome. The parameters,
per configuration file:

    n                genomes
    s_bottom         bottom-k sketch size
    s_scaled         scaled-sketch depth of the genus' root (a genome has a
                     little more: its accessory hashes)
    groups           species-level groups
    largest_share    [low, high]: share of the genomes in the largest group
    smallest_share   least share of any group
    lineage_size, strain_size   mean members of a lineage, of a strain clade
    ani_edge         {"species" | "lineage" | "strain" | "genome": [low, high]}
    accessory_max    largest share of own hashes a genome adds

Importing this module imports neither jax nor the program; ``prepare``
writes the workdir with the program's own writers (through
``planted_sketches.write_workdir``), because a workdir in the program's
format is the program's input.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np


@dataclass
class PlantedSpecies:
    names: list[str]
    bottom: list[np.ndarray]  # sorted unique uint64, s_bottom each
    scaled: list[np.ndarray]  # sorted unique uint64
    primary_labels: np.ndarray  # planted primary cluster of each genome: all 0
    labels: np.ndarray  # planted species group of each genome
    lineages: np.ndarray  # planted lineage of each genome (numbered over the collection)
    k: int
    s_bottom: int


def _split(rng: np.random.Generator, total: int, mean: float) -> list[int]:
    """`total` members in about total/mean parts of skewed sizes, none empty."""
    parts = max(1, min(total, int(round(total / mean))))
    sizes = 1 + rng.multinomial(total - parts, rng.dirichlet(np.full(parts, 2.0)))
    return [int(s) for s in sizes]


def group_sizes(rng: np.random.Generator, params: dict) -> list[int]:
    """Sizes of the species groups, largest first: the largest holds
    `largest_share` of the genomes, the rest are skewed and none is under
    `smallest_share`."""
    n, groups = int(params["n"]), int(params["groups"])
    least = max(2, int(np.ceil(n * float(params["smallest_share"]))))
    lo, hi = params["largest_share"]
    first = int(round(n * rng.uniform(lo, hi)))
    rest = n - first - least * (groups - 1)
    if rest < 0:
        raise ValueError("the shares leave no room for the smaller groups")
    tail = least + rng.multinomial(rest, rng.dirichlet(np.full(groups - 1, 1.0)))
    return [first] + sorted((int(t) for t in tail), reverse=True)


class _Tree:
    """Hash sets descending by edges of identity e."""

    def __init__(self, rng: np.random.Generator, params: dict):
        self.rng = rng
        self.k = int(params["kmer_size"])
        self.top = np.uint64(2**64 // int(params["scale"]))  # a scaled sketch holds hashes under this
        self.edges = params["ani_edge"]

    def fresh(self, count: int) -> np.ndarray:
        return self.rng.integers(0, self.top, size=count, dtype=np.uint64)

    def child(self, parent: np.ndarray, level: str, extra: int = 0) -> np.ndarray:
        e = self.rng.uniform(*self.edges[level])
        kept = parent[self.rng.random(len(parent)) < e**self.k]
        return np.concatenate([kept, self.fresh(len(parent) - len(kept) + extra)])


def generate(params: dict, seed: int) -> PlantedSpecies:
    """The planted collection, a pure function of (params, seed)."""
    rng = np.random.default_rng(seed)
    tree = _Tree(rng, params)
    s_b, s_s = int(params["s_bottom"]), int(params["s_scaled"])
    root = tree.fresh(s_s)
    names, bottoms, scaleds, labels, lineages = [], [], [], [], []
    lineage = 0
    for group, size in enumerate(group_sizes(rng, params)):
        species = tree.child(root, "species")
        for lineage_members in _split(rng, size, float(params["lineage_size"])):
            clade = tree.child(species, "lineage")
            for strain_members in _split(rng, lineage_members, float(params["strain_size"])):
                strain = tree.child(clade, "strain")
                for _ in range(strain_members):
                    extra = int(rng.uniform(0.0, float(params["accessory_max"])) * s_s)
                    scaled = np.unique(tree.child(strain, "genome", extra))
                    scaleds.append(scaled)
                    bottoms.append(scaled[:s_b])
                    names.append(f"synth_{len(names)}.fasta")
                    labels.append(group)
                    lineages.append(lineage)
            lineage += 1
    n = len(names)
    return PlantedSpecies(names, bottoms, scaleds, np.zeros(n, np.int64),
                          np.array(labels, np.int64), np.array(lineages, np.int64),
                          k=tree.k, s_bottom=s_b)


def prepare(cfg: dict, seed: int, out_dir: str) -> dict:
    """What a batch cell needs: the pristine workdir under `out_dir` and the
    planted data (with both label arrays) for the reference."""
    from benchmark import cells

    data = generate(cfg["data"], seed)
    wd = os.path.join(out_dir, "pristine")
    writer = cells.load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                            "planted_sketches.py"))
    writer.write_workdir(data, wd, cfg["data"])
    return {"workdir": wd, "data": data}

"""Planted release: one chip's part of a whole reference-database release,
given as MinHash sketches, in which THE SEED DRAWS THE HASH VALUES AND
NOTHING ELSE.

The sizes of a release's species are facts of the release, not of a random
draw, so they stand in the configuration file as a table (`clusters`): for
each size of primary cluster how many there are, and the sizes of the
secondary groups inside one. Everything else that decides how much work a
job does is laid out from that table and `layout_seed` alone (``plan``): the
tree inside every group, the identity along every edge of it, every genome's
accessory hashes and so its size and its `n_kmers`, and the place of every
genome in the input order. Two seeds give the same slot table, byte for byte
(``Plan.slot_table``), the same hash count in every genome, the same visiting
order in every cluster; they differ in which 64-bit values the hashes are.

A primary cluster is a tree of hash sets, cluster -> group -> lineage ->
strain clade -> genome, by ``planted_species.py``'s recipe: a node's scaled
sketch is its parent's with a share e^k of the hashes kept (here an exact
count, not a coin a hash) and the rest replaced by hashes of the node's own,
so the ANI of two genomes is about the product of the edges' e along the path
between them: 0.968-0.999 inside a group, 0.928-0.944 across the groups of a
cluster (Mash under 0.1), and clusters share nothing. A genome adds `extra`
hashes of its own (at most `accessory_max` of the root's depth, distinct
inside a cluster), which makes coverage directional and `n_kmers` distinct
inside a cluster: largest-first is one order. A cluster of one genome is
`s_scaled` + `extra` fresh hashes. The bottom sketch is the `s_bottom`
smallest hashes of the scaled sketch. The parameters, per configuration file:

    n, s_bottom, s_scaled, kmer_size, scale, hash
    clusters        [{"size", "count", "groups": [sizes adding up to size]}]
    layout_seed     the one draw of the layout (trees, edges, extras, order)
    lineage_size, strain_size, ani_edge, accessory_max   as planted_species.py

Importing this module imports neither jax nor the program; ``write_workdir``
uses the program's own writers, because a workdir in the program's format is
the program's input.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np

CONTIGS = 100


@dataclass
class PlantedRelease:
    names: list[str]
    bottom: list[np.ndarray]  # sorted unique uint64, s_bottom each
    scaled: list[np.ndarray]  # sorted unique uint64
    primary_labels: np.ndarray  # planted primary cluster of each genome
    labels: np.ndarray  # planted secondary group of each genome (numbered over the collection)
    length: np.ndarray  # bp, each genome's own
    n_kmers: np.ndarray  # what the greedy order sorts by
    k: int
    s_bottom: int


@dataclass
class Plan:
    """The layout: what no seed moves. `trees` holds one entry a primary
    cluster of two or more, in the order the hashes are drawn: [(e_species,
    [(e_lineage, [(e_strain, [(e_genome, slot)])])])]; `singles` the slots
    of the clusters of one. A slot is a place in the input order."""

    cluster: np.ndarray  # [n] primary cluster of each slot
    group: np.ndarray  # [n] secondary group of each slot, numbered over the collection
    extra: np.ndarray  # [n] accessory hashes of each slot
    n_kmers: np.ndarray  # [n]
    trees: list
    singles: list[int]

    def slot_table(self) -> bytes:
        """(cluster, group, n_kmers) of every slot, as bytes."""
        return np.stack([self.cluster, self.group, self.n_kmers]).astype(np.int64).tobytes()


def _split(rng: np.random.Generator, total: int, mean: float) -> list[int]:
    """`total` members in about total/mean parts of skewed sizes, none empty
    (``planted_species._split``)."""
    parts = max(1, min(total, int(round(total / mean))))
    sizes = 1 + rng.multinomial(total - parts, rng.dirichlet(np.full(parts, 2.0)))
    return [int(s) for s in sizes]


def plan(params: dict) -> Plan:
    """The layout, a pure function of the configuration: no seed reaches it."""
    rng = np.random.default_rng(int(params["layout_seed"]))
    n, s_s = int(params["n"]), int(params["s_scaled"])
    sizes = [c["size"] for c in params["clusters"] for _ in range(int(c["count"]))]
    if sum(sizes) != n or any(sum(c["groups"]) != c["size"] for c in params["clusters"]):
        raise ValueError("the size tables do not add up to n")
    edges = params["ani_edge"]
    # a release lists its genomes by accession, not by taxon: scatter them, once
    place = rng.permutation(n)
    cluster, group = np.zeros(n, np.int64), np.zeros(n, np.int64)
    extra = np.zeros(n, np.int64)
    trees, singles = [], []
    at = n_groups = 0
    top = int(float(params["accessory_max"]) * s_s)
    for ci, entry in enumerate(c for c in params["clusters"] for _ in range(int(c["count"]))):
        m = int(entry["size"])
        slots = place[at:at + m]
        at += m
        cluster[slots] = ci
        # distinct inside a cluster: sizes, and so `n_kmers`, never tie there
        extra[slots] = rng.choice(top + 1, size=m, replace=False)
        if m == 1:
            group[slots] = n_groups
            n_groups += 1
            singles.append(int(slots[0]))
            continue
        tree, used = [], 0
        for g_size in entry["groups"]:
            lineages = []
            for in_lineage in _split(rng, int(g_size), float(params["lineage_size"])):
                strains = []
                for in_strain in _split(rng, in_lineage, float(params["strain_size"])):
                    members = slots[used:used + in_strain]
                    used += in_strain
                    group[members] = n_groups
                    strains.append((rng.uniform(*edges["strain"]),
                                    [(rng.uniform(*edges["genome"]), int(s)) for s in members]))
                lineages.append((rng.uniform(*edges["lineage"]), strains))
            tree.append((rng.uniform(*edges["species"]), lineages))
            n_groups += 1
        trees.append(tree)
    n_kmers = int(params["scale"]) * (s_s + extra) - CONTIGS * (int(params["kmer_size"]) - 1)
    return Plan(cluster, group, extra, n_kmers, trees, singles)


def generate(params: dict, seed: int) -> PlantedRelease:
    """The planted collection: the layout of ``plan(params)``, its hash values
    drawn from `seed`."""
    laid = plan(params)
    rng = np.random.default_rng(seed)
    k, s_b, s_s = int(params["kmer_size"]), int(params["s_bottom"]), int(params["s_scaled"])
    top = np.uint64(2**64 // int(params["scale"]))  # a scaled sketch holds hashes under this

    def fresh(count: int) -> np.ndarray:
        return rng.integers(0, top, size=count, dtype=np.uint64)

    def child(parent: np.ndarray, e: float, more: int = 0) -> np.ndarray:
        keep = int(round(len(parent) * e**k))  # an exact count: the seed picks which, not how many
        kept = parent[rng.permutation(len(parent))[:keep]]
        return np.concatenate([kept, fresh(len(parent) - keep + more)])

    scaled: list = [None] * len(laid.cluster)
    for tree in laid.trees:
        root = fresh(s_s)
        for e_species, lineages in tree:
            species = child(root, e_species)
            for e_lineage, strains in lineages:
                clade = child(species, e_lineage)
                for e_strain, genomes in strains:
                    strain = child(clade, e_strain)
                    for e_genome, slot in genomes:
                        scaled[slot] = np.unique(child(strain, e_genome, int(laid.extra[slot])))
    for slot in laid.singles:
        scaled[slot] = np.unique(fresh(s_s + int(laid.extra[slot])))
    length = np.array([len(s) for s in scaled], np.int64) * int(params["scale"])
    return PlantedRelease(
        names=[f"synth_{i}.fasta" for i in range(len(scaled))],
        bottom=[s[:s_b] for s in scaled], scaled=scaled,
        primary_labels=laid.cluster, labels=laid.group,
        length=length, n_kmers=laid.n_kmers, k=k, s_bottom=s_b)


def write_workdir(data: PlantedRelease, wd_path: str, params: dict) -> None:
    """A workdir whose Bdb and sketch cache are planted
    (``planted_sketches.write_workdir``), with each genome's own `length` and
    `n_kmers` in the Gdb: the program's supported resume state, so ``compare
    <wd>`` (no -g) starts at the cluster stage."""
    import pandas as pd

    from drep_tpu.ingest import GenomeSketches, _save, sketch_args_snapshot
    from drep_tpu.workdir import WorkDirectory

    n = len(data.names)
    gdb = pd.DataFrame({
        "genome": data.names, "length": data.length,
        "N50": np.full(n, 50_000, np.int64), "contigs": np.full(n, CONTIGS, np.int64),
        "n_kmers": data.n_kmers,
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
    planted data (both label arrays, the sizes) for the reference."""
    data = generate(cfg["data"], seed)
    wd = os.path.join(out_dir, "pristine")
    write_workdir(data, wd, cfg["data"])
    return {"workdir": wd, "data": data}

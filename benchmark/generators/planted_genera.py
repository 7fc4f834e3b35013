"""Planted genera: ``planted_release.py``'s release with one level added
above the primary cluster, the GENUS, in which THE SEED DRAWS THE HASH VALUES
AND NOTHING ELSE.

Species are discrete, genera are not: a species-rich genus is a gradient of
species 0.80-0.95 ANI apart with no gap at dRep's primary cut, so at `-pa 0.9`
it is one connected component of the Mash graph that average linkage has to
cut into several primary clusters. A genus is planted as a CHAIN of
primary-cluster roots:

    root_1 = fresh hashes
    mid_j = child(root_j, mid_edge)          part-way along the chain edge
    root_{j+1} = child(mid_j, chain_edge)

and under every root the star of species that ``planted_release.py`` plants
(species -> lineage -> strain clade -> genome, accessory hashes, `n_kmers`
distinct inside a cluster, bottom sketch = the `s_bottom` smallest of the
scaled sketch, input order scattered once). -ln(ANI) adds up along a path of
the tree, so with the species edge 0.98 two species of one cluster lie about
0.065 apart, of neighbouring clusters 0.065 - ln(mid_edge x chain_edge), two
steps apart twice that step further: retained by the streaming primary (under
0.25) and never clustered; from four steps on over 0.25, unobserved.

Two things make the genus ONE component of the cutoff graph and not a clique:

- `lean`: a few species of a cluster hang from `mid_j` and not from `root_j`,
  so they lie nearer the next cluster than their siblings do (the gradient);
- `bridge`: for every link of the chain, pairs (a species of cluster j, a
  species of cluster j+1) of which `bridge_genomes` genomes each (fewer than
  the species holds) carry one POOL of `bridge_hashes` hashes more, the same
  pool on both sides: an island that crossed the species border, as
  horizontal transfer leaves them. Two genomes that hold the pool lie under
  the cutoff from each other, whatever the chain edge, and about 0.005
  further from everything else (they are larger); their ANI to their own
  species does not move (it is the larger of two coverages). No tree can give
  that: in a tree d(u, own cluster) + d(u, next cluster) is fixed by the chain
  edge, so a species under 0.085 from the next cluster is over the cutoff from
  its own. And the pool has to sit in genomes, not in species nodes: two
  species whose every genome pair is close merge with each other before they
  join their clusters, one time in a hundred at a bottom-1,000 sketch's
  noise; genomes of a species merge with their siblings first (0.03), and the
  species as a whole stays far from its bridge partner.

Everything that decides how much work a job does is laid out from the tables
and `layout_seed` alone (``plan``), as in ``planted_release.py``: two seeds give
the same ``Plan.slot_table``, the same hash count in every genome; they differ
in which 64-bit values the hashes are. Parameters, per configuration file:

    n, s_bottom, s_scaled, kmer_size, scale, hash
    types           {name: {"species": [genomes of each species],
                            "lean": [species hung from the mid node],
                            "next": [...], "prev": [...]}}: a kind of primary
                    cluster; `next[b]` of cluster j and `prev[b]` of cluster
                    j+1 are the b-th bridge of their link
    genera          [{"chain": [type names], "count", "mid_edge", "chain_edge",
                      "bridge_genomes", "bridge_hashes"}]
    layout_seed, lineage_size, strain_size, ani_edge, accessory_max
                    as planted_release.py

``generate`` returns ``planted_release.PlantedRelease``'s fields (`labels` are
the species, `primary_labels` the planted primary clusters) plus `genus` and
`links`, and ``write_workdir`` is ``planted_release``'s. Importing this
module imports neither jax nor the program.
"""

from __future__ import annotations

import importlib.util
import os
import sys
from dataclasses import dataclass, field

import numpy as np

def _beside(name: str):
    """A generator beside this one, loaded by path as ``cells.load_module`` loads this one."""
    mod_name = "bench_" + name
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(
        mod_name, os.path.join(os.path.dirname(os.path.abspath(__file__)), name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


_release = _beside("planted_release")
_split, CONTIGS = _release._split, _release.CONTIGS
write_workdir = _release.write_workdir


@dataclass
class PlantedGenera(_release.PlantedRelease):
    genus: np.ndarray = None  # planted genus of each genome (a cluster of one is its own)
    # one entry a link of a chain: (cluster j, cluster j+1), planted cluster numbers
    links: list = field(default_factory=list)


@dataclass
class Plan:
    """The layout: what no seed moves. `genera` holds one entry a genus of two
    or more genomes, in the order the hashes are drawn: {"mid_edge",
    "chain_edge", "pool": hashes of a bridge's pool, "bridges": [bridges of
    each link], "clusters": [[species]]}, a species {"edge", "lean",
    "lineages": [(e_lineage, [(e_strain, [(e_genome, slot, pool)])])]} where
    `pool` is None or ("next" | "prev", bridge); `singles` the slots of the
    clusters of one. A slot is a place in the input order."""

    cluster: np.ndarray  # [n] primary cluster of each slot
    group: np.ndarray  # [n] species of each slot, numbered over the collection
    genus: np.ndarray  # [n]
    extra: np.ndarray  # [n] accessory hashes of each slot
    n_kmers: np.ndarray  # [n]
    genera: list
    singles: list[int]
    links: list[tuple[int, int]]

    def slot_table(self) -> bytes:
        """(genus, cluster, group, n_kmers) of every slot, as bytes."""
        return np.stack([self.genus, self.cluster, self.group,
                         self.n_kmers]).astype(np.int64).tobytes()


def table_sums(params: dict) -> dict:
    """What the tables add up to: genomes, primary clusters, species, loose
    components (chains of two or more clusters) and the genomes in them."""
    out = {"genomes": 0, "clusters": 0, "clusters_of_two_or_more": 0, "species": 0,
           "loose_components": 0, "rows_loose": 0}
    for genus in params["genera"]:
        kinds = [params["types"][t]["species"] for t in genus["chain"]]
        count, rows = int(genus["count"]), sum(sum(k) for k in kinds)
        out["genomes"] += count * rows
        out["clusters"] += count * len(kinds)
        out["clusters_of_two_or_more"] += count * sum(sum(k) > 1 for k in kinds)
        out["species"] += count * sum(len(k) for k in kinds)
        if len(kinds) > 1:
            out["loose_components"] += count
            out["rows_loose"] += count * rows
    return out


def plan(params: dict) -> Plan:
    """The layout, a pure function of the configuration: no seed reaches it."""
    rng = np.random.default_rng(int(params["layout_seed"]))
    n, s_s = int(params["n"]), int(params["s_scaled"])
    if table_sums(params)["genomes"] != n:
        raise ValueError("the tables do not add up to n")
    edges, types = params["ani_edge"], params["types"]
    place = rng.permutation(n)  # a release lists its genomes by accession, not by taxon
    cluster, group, genus_of = (np.zeros(n, np.int64) for _ in range(3))
    extra = np.zeros(n, np.int64)
    genera, singles, links = [], [], []
    at = n_clusters = n_groups = n_genera = 0
    top = int(float(params["accessory_max"]) * s_s)
    for entry in (g for g in params["genera"] for _ in range(int(g["count"]))):
        chain = [types[t] for t in entry["chain"]]
        carry, pool = int(entry.get("bridge_genomes", 0)), int(entry.get("bridge_hashes", 0))
        for j, (a, b) in enumerate(zip(chain, chain[1:])):
            ends = [a["species"][sp] for sp in a.get("next", [])] + [b["species"][sp] for sp in b.get("prev", [])]
            if not ends or len(a["next"]) != len(b["prev"]) or min(ends) <= carry or pool <= top:
                raise ValueError("a link needs as many `next` species as `prev` species, at least one, each "
                                 "of more genomes than carry the pool, and a pool larger than any accessory")
            if set(a["next"]) & (set(a.get("lean", [])) | (set(a.get("prev", [])) if j else set())):
                raise ValueError("a species is one bridge's end, or leans, and no two of these")
        laid = []
        for j, kind in enumerate(chain):
            m = sum(kind["species"])
            slots = place[at:at + m]
            at += m
            cluster[slots], genus_of[slots] = n_clusters, n_genera
            # distinct inside a cluster: sizes, and so `n_kmers`, never tie there
            extra[slots] = rng.choice(top + 1, size=m, replace=False)
            if j:
                links.append((n_clusters - 1, n_clusters))
            n_clusters += 1
            if len(chain) == 1 and m == 1:
                group[slots] = n_groups
                n_groups += 1
                singles.append(int(slots[0]))
                continue
            has_next, has_prev = j + 1 < len(chain), j > 0
            tree, used = [], 0
            for sp, g_size in enumerate(kind["species"]):
                bridge = None
                if has_next and sp in kind.get("next", []):
                    bridge = ("next", kind["next"].index(sp))
                elif has_prev and sp in kind.get("prev", []):
                    bridge = ("prev", kind["prev"].index(sp))
                lineages, first = [], used
                for in_lineage in _split(rng, int(g_size), float(params["lineage_size"])):
                    strains = []
                    for in_strain in _split(rng, in_lineage, float(params["strain_size"])):
                        members = slots[used:used + in_strain]
                        group[members] = n_groups
                        # the species' first genomes carry its bridge's pool
                        strains.append((rng.uniform(*edges["strain"]),
                                        [(rng.uniform(*edges["genome"]), int(s),
                                          bridge if bridge and used + x - first < carry else None)
                                         for x, s in enumerate(members)]))
                        used += in_strain
                    lineages.append((rng.uniform(*edges["lineage"]), strains))
                if bridge:
                    extra[slots[first:first + carry]] += pool
                n_groups += 1
                tree.append({"edge": rng.uniform(*edges["species"]),
                             "lean": has_next and sp in kind.get("lean", []), "lineages": lineages})
            laid.append(tree)
        n_genera += 1
        if laid:
            genera.append({"mid_edge": float(entry.get("mid_edge", 1.0)),
                           "chain_edge": float(entry.get("chain_edge", 1.0)), "pool": pool,
                           "bridges": [len(kind.get("next", [])) for kind in chain[:-1]],
                           "clusters": laid})
    n_kmers = int(params["scale"]) * (s_s + extra) - CONTIGS * (int(params["kmer_size"]) - 1)
    return Plan(cluster, group, genus_of, extra, n_kmers, genera, singles, links)


def generate(params: dict, seed: int) -> PlantedGenera:
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
    for genus in laid.genera:
        root, pools = fresh(s_s), {}
        for j, tree in enumerate(genus["clusters"]):
            has_next = j + 1 < len(genus["clusters"])
            mid = child(root, genus["mid_edge"]) if has_next else None
            # the pools this cluster shares with the one before it, then those with the next
            pools = {("prev", b): pool for (_, b), pool in pools.items()}
            if has_next:
                pools.update({("next", b): fresh(genus["pool"]) for b in range(genus["bridges"][j])})
            for sp in tree:
                species = child(mid if sp["lean"] else root, sp["edge"])
                for e_lineage, strains in sp["lineages"]:
                    clade = child(species, e_lineage)
                    for e_strain, genomes in strains:
                        strain = child(clade, e_strain)
                        for e_genome, slot, bridge in genomes:
                            more = int(laid.extra[slot]) - (genus["pool"] if bridge else 0)
                            own = child(strain, e_genome, more)
                            scaled[slot] = np.unique(np.concatenate([own, pools[bridge]]) if bridge else own)
            if has_next:
                root = child(mid, genus["chain_edge"])
                pools = {key: pool for key, pool in pools.items() if key[0] == "next"}
    for slot in laid.singles:
        scaled[slot] = np.unique(fresh(s_s + int(laid.extra[slot])))
    length = np.array([len(s) for s in scaled], np.int64) * int(params["scale"])
    return PlantedGenera(
        names=[f"synth_{i}.fasta" for i in range(len(scaled))],
        bottom=[s[:s_b] for s in scaled], scaled=scaled,
        primary_labels=laid.cluster, labels=laid.group,
        length=length, n_kmers=laid.n_kmers, k=k, s_bottom=s_b,
        genus=laid.genus, links=laid.links)


def prepare(cfg: dict, seed: int, out_dir: str) -> dict:
    """What a batch cell needs: the pristine workdir under `out_dir` and the
    planted data (the label arrays, the sizes, the links) for the reference."""
    data = generate(cfg["data"], seed)
    wd = os.path.join(out_dir, "pristine")
    write_workdir(data, wd, cfg["data"])
    return {"workdir": wd, "data": data}

"""Planted FASTA collection: the bins of one metagenomic study, as files.

The only generator whose output is sequence: `dereplicate` starts at the
FASTA bytes, so lengths, contigs, `N` runs, lower case, completeness and
contamination are in the files and not in a table beside them. The tree, in
sequence (``planted_species.py`` has it in hashes):

    root      an unrelated random genome; its length is log-normal
              (`length_median`, `length_sigma`, clipped to `length_clip`)
              and all lengths are scaled so that the files hold
              `total_bases` whatever the seed: every seed is the same work
    group     the root with point substitutions at identity
              `identity_group_edge`, cut into contigs (`contig_scale`,
              `contigs`): a secondary cluster. A root has 1-3 groups
              (`groups_per_root`), so a primary cluster holds 1-3 secondary
              clusters at identity about edge^2 across
    member    `completeness` of the group's contigs (whole contigs, drawn
              without order), substituted at identity `identity_member_edge`,
              plus `contamination` of the group's length in contigs of an
              organism that has no bin of its own (fresh random sequence).
              Group sizes follow `cluster_law`
    short bin `short_share` of the files: a complete genome of
              `short_length` bases (a plasmid, a phage), a root of its own.
              Only the length filter drops it

`genomeInfo.csv` lists, a genome, the completeness and the contamination
that the file really has (kept bases and foreign bases over the group's
length, in percent, two decimals), as CheckM would estimate them. One file in
`n_run_share` carries runs of `N` and a few IUPAC codes, one in
`lowercase_share` soft-masked (lower-case) stretches. Lines are `line_width`
characters.

``plan`` is a pure function of (params, seed) and decides everything but the
bases; ``prepare`` writes the files, a root's family at a time, on a process
pool. Importing this module imports neither jax nor the program.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import numpy as np

ACGT = np.frombuffer(b"ACGT", np.uint8)
IUPAC = np.frombuffer(b"RYKMSWN", np.uint8)
INLINE_BASES = 50e6  # below this the files are written in this process


@dataclass
class PlantedFasta:
    names: list[str]  # file names, in the order the job is given them
    paths: list[str]
    genome_info: str  # path of genomeInfo.csv
    completeness: np.ndarray  # as the table lists them
    contamination: np.ndarray
    short: np.ndarray  # bool: a short bin, under the length filter
    primary_labels: np.ndarray  # planted root of each genome
    labels: np.ndarray  # planted group (secondary cluster) of each genome
    bases: np.ndarray  # characters of sequence in each file
    k: int
    s_bottom: int


# ---- the plan: everything but the bases -------------------------------------------


def _group_size(rng: np.random.Generator, law: dict) -> int:
    if law["law"] != "geometric":
        raise ValueError(f"unknown cluster_law {law!r}")
    return min(int(rng.geometric(law["p"])), int(law["cap"]))


def plan(params: dict, seed: int) -> list[dict]:
    """One task a root: {"root", "seed", "length", "contig_scale", "groups":
    [{"label", "edge", "members": [{"index", "name", "completeness" (share
    aimed at), "contamination", "edge", "n_runs", "lowercase"}]}]}. A short
    bin is a root with one group of one complete member. `index` is the
    genome's place in the job's list: drawn, so that a cluster's members do
    not sit side by side."""
    rng = np.random.default_rng(seed)
    n = int(params["n"])
    n_short = int(round(n * float(params["short_share"])))
    order = rng.permutation(n)
    comp, cont = params["completeness"], params["contamination"]
    tasks: list[dict] = []
    placed = label = 0
    while placed < n - n_short:
        groups = []
        for _ in range(1 + int(rng.choice(len(params["groups_per_root"]), p=params["groups_per_root"]))):
            size = min(_group_size(rng, params["cluster_law"]), n - n_short - placed)
            if size == 0:
                break
            members = [{
                "index": int(order[placed + i]),
                "completeness": 1.0 - (1.0 - comp["floor"] / 100.0) * rng.random() ** comp["power"],
                "contamination": cont["ceiling"] / 100.0 * rng.random() ** cont["power"],
                "edge": rng.uniform(*params["identity_member_edge"]),
                "n_runs": bool(rng.random() < params["n_run_share"]),
                "lowercase": bool(rng.random() < params["lowercase_share"]),
            } for i in range(size)]
            groups.append({"label": label, "edge": rng.uniform(*params["identity_group_edge"]),
                           "members": members})
            placed += size
            label += 1
        lo, hi = params["length_clip"]
        tasks.append({"length": float(np.clip(rng.lognormal(np.log(params["length_median"]),
                                                            params["length_sigma"]), lo, hi)),
                      "contig_scale": float(np.exp(rng.uniform(*np.log(params["contig_scale"])))),
                      "groups": groups})
    # every seed is the same work: the lengths are scaled to the stated total
    total = sum(t["length"] * (m["completeness"] + m["contamination"])
                for t in tasks for g in t["groups"] for m in g["members"])
    short_total = 0.0
    shorts = []
    for i in range(n_short):
        length = float(rng.uniform(*params["short_length"]))
        short_total += length
        shorts.append({"length": length, "contig_scale": length, "groups": [{
            "label": label + i, "edge": 1.0, "members": [{
                "index": int(order[n - n_short + i]), "completeness": 1.0, "contamination": 0.0,
                "edge": 1.0, "n_runs": False, "lowercase": False, "short": True}]}]})
    scale = (float(params["total_bases"]) - short_total) / total
    for t in tasks:
        t["length"] = float(np.clip(t["length"] * scale, *params["length_clip"]))
    tasks += shorts
    for r, t in enumerate(tasks):
        t["root"], t["seed"], t["length"] = r, [int(seed), r], int(t["length"])
        for g in t["groups"]:
            for m in g["members"]:
                m["name"] = f"bin_{m['index']:04d}.fasta"
    return tasks


# ---- the bases ------------------------------------------------------------------


def _substitute(rng: np.random.Generator, seq: np.ndarray, identity: float) -> np.ndarray:
    """`seq` (codes 0-3) with each base replaced by another with probability
    1 - identity."""
    out = seq.copy()
    if identity < 1.0:
        hit = np.flatnonzero(rng.random(len(seq)) < 1.0 - identity)
        out[hit] = (out[hit] + rng.integers(1, 4, len(hit), dtype=np.uint8)) & 3
    return out


def _cut(rng: np.random.Generator, total: int, pieces: int, least: int) -> np.ndarray:
    """`total` bases in `pieces` contigs of skewed lengths, none under `least`."""
    pieces = max(1, min(pieces, total // least))
    share = rng.exponential(size=pieces)
    lens = least + np.floor(share / share.sum() * (total - least * pieces)).astype(np.int64)
    lens[-1] += total - lens.sum()
    return lens


def _mask(rng: np.random.Generator, text: np.ndarray, member: dict) -> None:
    """Runs of N with a few IUPAC codes, and lower-case stretches, in place."""
    n = len(text)
    if member["n_runs"]:
        for _ in range(int(rng.integers(3, 9))):
            at, run = int(rng.integers(0, n)), int(np.exp(rng.uniform(np.log(10), np.log(2000))))
            text[at:at + run] = ord("N")
        text[rng.integers(0, n, 4)] = IUPAC[rng.integers(0, len(IUPAC), 4)]
    if member["lowercase"]:
        for _ in range(int(rng.integers(3, 11))):
            at, run = int(rng.integers(0, n)), int(np.exp(rng.uniform(np.log(200), np.log(20000))))
            text[at:at + run] |= 32  # upper to lower case; N becomes n: still no base


def _write_fasta(path: str, name: str, text: np.ndarray, lens: np.ndarray, width: int) -> None:
    newline = np.uint8(10)
    with open(path, "wb") as f:
        at = 0
        for c, length in enumerate(lens):
            seq = text[at:at + length]
            at += length
            f.write(f">{name[:-6]}_contig_{c + 1} length={length}\n".encode())
            full = length // width * width
            body = np.empty((full // width, width + 1), np.uint8)
            body[:, :width] = seq[:full].reshape(-1, width)
            body[:, width] = newline
            f.write(body.tobytes())
            if full < length:
                f.write(seq[full:].tobytes() + b"\n")


def plant_root(job: tuple) -> list[dict]:
    """Write the files of one root's family. Returns, a file, {"index",
    "name", "root", "label", "completeness", "contamination" (percent, as
    realised), "bases", "contigs", "short"}."""
    task, params, fasta_dir = job
    rng = np.random.default_rng(task["seed"])
    root = rng.integers(0, 4, task["length"], dtype=np.uint8)
    lo, hi = params["contigs"]
    least = int(params["contig_min"])
    out = []
    for group in task["groups"]:
        ancestor = _substitute(rng, root, group["edge"])
        pieces = int(np.clip(round(len(root) / task["contig_scale"]), lo, hi))
        lens = _cut(rng, len(root), pieces, least)
        starts = np.concatenate([[0], np.cumsum(lens)[:-1]])
        for member in group["members"]:
            if member.get("short"):
                kept = np.arange(len(lens))
            else:  # whole contigs in a drawn order until the share aimed at is reached
                perm = rng.permutation(len(lens))
                reach = np.cumsum(lens[perm]) >= member["completeness"] * len(root)
                kept = np.sort(perm[:int(np.argmax(reach)) + 1])
            own = np.concatenate([ancestor[starts[c]:starts[c] + lens[c]] for c in kept])
            own = _substitute(rng, own, member["edge"])
            foreign = int(member["contamination"] * len(root))
            foreign_lens = (_cut(rng, foreign, max(1, round(foreign / task["contig_scale"])), least)
                            if foreign >= least else np.zeros(0, np.int64))
            codes = np.concatenate([own, rng.integers(0, 4, int(foreign_lens.sum()), dtype=np.uint8)])
            text = ACGT[codes]
            _mask(rng, text, member)
            all_lens = np.concatenate([lens[kept], foreign_lens])
            _write_fasta(os.path.join(fasta_dir, member["name"]), member["name"], text, all_lens,
                         int(params["line_width"]))
            out.append({"index": member["index"], "name": member["name"], "root": task["root"],
                        "label": group["label"], "short": bool(member.get("short")),
                        "completeness": 100.0 * len(own) / len(root),
                        "contamination": 100.0 * float(foreign_lens.sum()) / len(root),
                        "bases": len(codes), "contigs": len(all_lens)})
    return out


# ---- the collection ---------------------------------------------------------------


def generate(params: dict, seed: int, out_dir: str, processes: int | None = None) -> PlantedFasta:
    """Write the collection under `out_dir` (``fasta/<name>``,
    ``genomeInfo.csv``) and return what was planted."""
    fasta_dir = os.path.join(out_dir, "fasta")
    os.makedirs(fasta_dir, exist_ok=True)
    tasks = plan(params, seed)
    # the largest families first, so that the pool ends level
    jobs = [(t, params, fasta_dir) for t in sorted(
        tasks, key=lambda t: -t["length"] * sum(len(g["members"]) for g in t["groups"]))]
    processes = min(processes or len(os.sched_getaffinity(0)), len(jobs))
    if processes <= 1 or float(params["total_bases"]) < INLINE_BASES:
        families = [plant_root(j) for j in jobs]
    else:
        import importlib
        import multiprocessing
        from concurrent.futures import ProcessPoolExecutor

        # spawned (the caller may hold an accelerator); the workers import this
        # file under its package name, whatever name the harness loaded it by
        worker = importlib.import_module("benchmark.generators.planted_fasta").plant_root
        with ProcessPoolExecutor(processes, mp_context=multiprocessing.get_context("spawn")) as pool:
            families = list(pool.map(worker, jobs))
    files = sorted((f for family in families for f in family), key=lambda f: f["index"])
    genome_info = os.path.join(out_dir, "genomeInfo.csv")
    with open(genome_info, "w") as f:
        f.write("genome,completeness,contamination\n")
        for row in files:
            f.write(f"{row['name']},{row['completeness']:.2f},{row['contamination']:.2f}\n")
    return PlantedFasta(
        names=[r["name"] for r in files],
        paths=[os.path.join(fasta_dir, r["name"]) for r in files],
        genome_info=genome_info,
        completeness=np.array([float(f"{r['completeness']:.2f}") for r in files]),
        contamination=np.array([float(f"{r['contamination']:.2f}") for r in files]),
        short=np.array([r["short"] for r in files], bool),
        primary_labels=np.array([r["root"] for r in files], np.int64),
        labels=np.array([r["label"] for r in files], np.int64),
        bases=np.array([r["bases"] for r in files], np.int64),
        k=int(params["kmer_size"]), s_bottom=int(params["s_bottom"]))


def prepare(cfg: dict, seed: int, out_dir: str) -> dict:
    """What a cell of kind `fasta_jobs` needs: the files, written once and
    shared read-only by every job, and what was planted."""
    return {"data": generate(cfg["data"], seed, os.path.join(out_dir, "planted"))}

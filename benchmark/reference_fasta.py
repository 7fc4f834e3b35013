"""The plain reference of a `dereplicate` job that starts at the FASTA files:
what drep-tpu's sketches, filtered set, clusters, scores and winners should
be, by NumPy and SciPy.

Independent of the program: nothing is imported from ``drep_tpu`` and nothing
it computed is read. The input is the FASTA bytes and the genomeInfo table the
benchmark planted; the pair arithmetic is ``reference.py``'s, through the
whole-cluster spelling of ``reference_species.py``. Everything is float64 on
the host. Semantics, as upstream dRep and Mash define them and the
configuration file states them:

- a FASTA: a line that starts with ``>`` opens a record; every other line is
  stripped of leading and trailing white space and appended to the record's
  sequence; a record with no sequence is no contig. `length` is the sum of the
  contigs' lengths (every character counts), `N50` the length of the contig
  at which the descending running sum first reaches half of `length`;
- a k-mer is valid iff each of its k characters is one of ``ACGT`` or
  ``acgt`` (lower case reads as upper case). Any other character (``N``, an
  IUPAC code, white space inside a line) makes the k windows over it invalid,
  and no window spans two contigs;
- a k-mer's value packs A=0 C=1 G=2 T=3, two bits a base, first base highest;
  the canonical value is the smaller of it and its reverse complement's; the
  hash is the splitmix64 finalizer of the canonical value;
- the scaled sketch is every distinct hash <= 2^64 // scale - 1, ascending;
  the bottom sketch the `sketch_size` smallest distinct hashes (when the
  scaled sketch holds that many, they are its first `sketch_size`);
- filter: length >= `length`, completeness >= `completeness`, contamination
  <= `contamination`; clusters as ``reference.py`` has them, over what passes;
- centrality of a genome: the mean ANI to the other members of its secondary
  cluster (0 alone); score = comW x completeness - conW x contamination
  + strW x strain heterogeneity (0: the table has none) + N50W x log10(N50)
  + sizeW x log10(length) + centW x (centrality - S_ani); the winner of a
  secondary cluster has the highest score, the first name on a tie.
"""

from __future__ import annotations

import os

import numpy as np

from benchmark import reference as ref
from benchmark import reference_species as refs

INVALID = 4  # code of a character that is no base
WINDOW_BLOCK = 1 << 14  # k-mer windows formed at a time
_CODE = np.full(256, INVALID, np.uint8)
for _i, _pair in enumerate((b"Aa", b"Cc", b"Gg", b"Tt")):
    _CODE[list(_pair)] = _i


# ---- one file ------------------------------------------------------------------


def read_contigs(path: str) -> list[bytes]:
    with open(path, "rb") as f:
        data = f.read()
    contigs, lines = [], []
    for line in data.split(b"\n"):
        if line.startswith(b">"):
            contigs.append(b"".join(lines))
            lines = []
        else:
            lines.append(line.strip())
    contigs.append(b"".join(lines))
    return [c for c in contigs if c]


def n50(lengths: list[int]) -> int:
    total, running = sum(lengths), 0
    for length in sorted(lengths, reverse=True):
        running += length
        if 2 * running >= total:
            return length
    return 0


def splitmix64(x: np.ndarray) -> np.ndarray:
    z = x.astype(np.uint64)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def kmer_hashes(contigs: list[bytes], k: int) -> np.ndarray:
    """The hash of the canonical packed value of every valid k-mer of the
    contigs, in order, duplicates kept. The contigs are laid end to end with one invalid
    character between two, so no window spans a boundary; the windows are
    taken a block at a time (small arrays: nothing else)."""
    codes = _CODE[np.frombuffer(b"|".join(contigs), np.uint8)]
    out = [np.zeros(0, np.uint64)]
    for lo in range(0, len(codes) - k + 1, WINDOW_BLOCK):
        part = codes[lo:lo + WINDOW_BLOCK + k - 1]
        n = len(part) - k + 1
        bad = np.concatenate([[0], np.cumsum(part == INVALID)])
        base = (part & 3).astype(np.uint64)
        fwd = np.zeros(n, np.uint64)
        rev = np.zeros(n, np.uint64)
        for j in range(k):  # window i holds characters i .. i + k - 1
            fwd = (fwd << np.uint64(2)) | base[j:j + n]
            rev |= (np.uint64(3) - base[j:j + n]) << np.uint64(2 * j)
        valid = bad[k:] == bad[:-k]  # no invalid character among the k
        out.append(splitmix64(np.minimum(fwd, rev)[valid]))
    return np.concatenate(out)


def sketch_file(path: str, k: int, sketch_size: int, scale: int) -> dict:
    """One genome: its assembly numbers and both sketches, from the bytes."""
    contigs = read_contigs(path)
    lengths = [len(c) for c in contigs]
    hashes = kmer_hashes(contigs, k)
    scaled = np.unique(hashes[hashes <= np.uint64(2**64 // scale - 1)])
    bottom = scaled[:sketch_size] if len(scaled) >= sketch_size else np.unique(hashes)[:sketch_size]
    return {"length": sum(lengths), "N50": n50(lengths), "contigs": len(contigs),
            "valid_kmers": len(hashes), "bottom": bottom, "scaled": scaled}


def _sketch_job(job) -> dict:
    return sketch_file(*job)


def sketch_files(paths: list[str], k: int, sketch_size: int, scale: int,
                 processes: int | None = None) -> list[dict]:
    """``sketch_file`` over many files, on a process pool of the reference's
    own (spawned: the caller may hold an accelerator)."""
    jobs = [(p, k, sketch_size, scale) for p in paths]
    processes = min(processes or len(os.sched_getaffinity(0)), len(jobs))
    if processes <= 1 or sum(os.path.getsize(p) for p in paths) < 20e6:
        return [_sketch_job(j) for j in jobs]
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    with ProcessPoolExecutor(processes, mp_context=multiprocessing.get_context("spawn")) as pool:
        return list(pool.map(_sketch_job, jobs))


# ---- the job -------------------------------------------------------------------


def passes_filter(stats: dict, quality: dict, params: dict) -> bool:
    return (stats["length"] >= params["length"]
            and quality["completeness"] >= params["completeness"]
            and quality["contamination"] <= params["contamination"])


def dereplicate(names: list[str], sketches: list[dict], quality: dict[str, dict], params: dict,
                lower_precision: bool = False) -> dict:
    """The answers of one job over the genomes `names` (all of them: the
    filter is applied here; a genome it drops may come without sketches).
    `lower_precision` is the control: Mash distances and ANIs rounded to
    bfloat16 before anything is derived from them.

    Returns {"kept": names that pass, in order; "dist", "ani", "cov":
    [m, m] over `kept`, ani and cov NaN outside a primary cluster, cov[i, j]
    the coverage of i by j; "primary", "secondary": {name: label}, a
    secondary label is (primary, number); "centrality", "score": {name:
    value}; "winners": {secondary label: name}}."""
    k, s = int(params["kmer_size"]), int(params["sketch_size"])
    index = [i for i, g in enumerate(names) if passes_filter(sketches[i], quality[g], params)]
    kept = [names[i] for i in index]
    m = len(kept)
    dist = refs.mash_matrix([sketches[i]["bottom"] for i in index], s, k, lower_precision)
    primary = refs.primary_labels(dist, 1.0 - params["P_ani"]) if m > 1 else np.ones(m, np.int64)
    ani = np.full((m, m), np.nan)
    cov = np.full((m, m), np.nan)
    secondary: dict[str, tuple] = {}
    centrality = {}
    for label in np.unique(primary):
        group = np.flatnonzero(primary == label)
        a, c, sec = refs.secondary_of_cluster([sketches[index[g]]["scaled"] for g in group], k,
                                              params["S_ani"], params["cov_thresh"], lower_precision)
        ani[np.ix_(group, group)] = a
        cov[np.ix_(group, group)] = c
        for x, g in enumerate(group):
            secondary[kept[g]] = (int(label), int(sec[x]))
            mates = [y for y in range(len(group)) if y != x and sec[y] == sec[x]]
            centrality[kept[g]] = float(np.mean(a[x, mates])) if mates else 0.0
    w = params["weights"]
    score = {}
    for i, g in zip(index, kept):
        score[g] = (w["completeness"] * quality[g]["completeness"]
                    - w["contamination"] * quality[g]["contamination"]
                    + w["N50"] * np.log10(max(sketches[i]["N50"], 1))
                    + w["size"] * np.log10(max(sketches[i]["length"], 1))
                    + w["centrality"] * (centrality[g] - params["S_ani"]))
    winners: dict[tuple, str] = {}
    for g in sorted(kept):  # the first name wins a tie
        best = winners.get(secondary[g])
        if best is None or score[g] > score[best]:
            winners[secondary[g]] = g
    return {"kept": kept, "dist": dist, "ani": ani, "cov": cov,
            "primary": {g: int(p) for g, p in zip(kept, primary)}, "secondary": secondary,
            "centrality": centrality, "score": score, "winners": winners}


def score_gaps(answers: dict) -> tuple[float, int]:
    """(the least gap between the two best scores of any secondary cluster
    whose two best differ, inf where there is none; the clusters whose two
    best tie exactly and go to the first name): how far a winner is from
    hanging on rounding."""
    by_cluster: dict = {}
    for g, label in answers["secondary"].items():
        by_cluster.setdefault(label, []).append(answers["score"][g])
    gaps = [float(np.subtract(*sorted(v, reverse=True)[:2])) for v in by_cluster.values() if len(v) > 1]
    return min([g for g in gaps if g > 0], default=float("inf")), sum(g == 0 for g in gaps)


def partition_mismatch(got: dict, want: dict) -> int:
    """Genomes in a cluster of `got` ({name: label}) that `want` lacks."""
    return ref.partition_mismatch(ref.partition_of(got), ref.partition_of(want))

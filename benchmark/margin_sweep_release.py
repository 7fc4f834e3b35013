#!/usr/bin/env python3
"""The reference and the geometry alone over many seeds, at full size, for a
configuration planted by ``generators/planted_release.py``: that every seed
gives the same WORK, and how far every quantity that is compared with a
threshold or rounded to a shape lies from its edge. Run on the CPU before any
chip time is spent; PERF.md quotes the output.

    python3 benchmark/margin_sweep_release.py --config gtdb_release_6k --seeds 0-15 [--rehearse]

For each seed, from ``reference_greedy`` (no program code runs):

- margins of the answer: the least ANI of a genome to its own group's
  representative over S_ani, S_ani over the largest ANI to another group's
  (the smaller of the two is the greedy decision nearest S_ani), the least
  coverage of a consumed pair over cov_thresh, the primary trees' merge
  nearest the cut, the genomes off the planted partitions;
- the work, which has to be equal over the seeds entry for entry (`work`):
  for each primary cluster over ``ENGINE_OVER`` genomes its rows, its blocks of
  ``BLOCK`` rows, its representatives, the representatives that exist before
  each block (summed: the engine's `rep_rows_real`), the pairs the scan
  consumes, its vocabulary chunks and each chunk's id width; for the smaller
  clusters their count, rows, consumed pairs, and the power-of-two bucket of
  each size class's largest vocabulary; the Ndb and Cdb rows;
- what the seed may move, and how far from an edge it stays: each engine
  cluster's vocabulary extent against the nearest multiple of ``V_CHUNK``
  (`extent_edge`, as a share of that multiple), each chunk's fullest row
  against the nearest power of two (`width_edge`), each small size class's
  vocabulary against the nearest power of two (`pack_edge`; 65,536 is also
  where the batched pack turns from uint16 to int32).

The program's rules the geometry copies (cluster/controller.py, cluster/
greedy.py, ops/containment.py::VocabChunkGeometry; a tier-1 test holds the
copy to the program at toy size): clusters over 32 go through the engine in
blocks of 128 against representative tiles of 512; a chunk holds 262,144
vocabulary ids (2^29 budget elements over 2 x 512 rows, rounded down to a
power of two); a chunk's id width is the power of two at or over its fullest
row, at least 128; the batched pack's indicator is the power of two at or
over the largest cluster vocabulary, at least 8,192.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark import cells  # noqa: E402
from benchmark import reference as ref  # noqa: E402
from benchmark import reference_greedy as rg  # noqa: E402
from benchmark import reference_species as refs  # noqa: E402
from benchmark.margin_sweep_species import _cut_gap  # noqa: E402

ENGINE_OVER = 32
BLOCK = 128
V_CHUNK = 262_144
WIDTH_MIN = 128
PACK_MIN = 8_192


def _pow2(x: int, least: int) -> int:
    return max(least, 1 << (max(int(x), 1) - 1).bit_length())


def _pow2_edge(x: int, least: int) -> float:
    """Distance from x to the nearest power of two at or over `least` (under
    which every size rounds to `least`), as a share of that power."""
    return min(abs(x - 2**j) / 2**j for j in range(least.bit_length() - 1, 40))


def engine_geometry(scaled: list[np.ndarray]) -> dict:
    """The chunk plan of one engine cluster: its vocabulary extent, the
    chunks of ``V_CHUNK`` ids, each chunk's fullest row and its width."""
    vocab = np.unique(np.concatenate(scaled))
    extent = len(vocab)
    chunks = max(1, -(-extent // V_CHUNK))
    fullest = np.zeros(chunks, np.int64)
    for s in scaled:
        fullest = np.maximum(fullest, np.bincount(np.searchsorted(vocab, s) // V_CHUNK,
                                                  minlength=chunks))
    nearest = max(1, round(extent / V_CHUNK)) * V_CHUNK
    return {"extent": extent, "chunks": chunks, "fullest": fullest.tolist(),
            "widths": [_pow2(f, WIDTH_MIN) for f in fullest],
            "extent_edge": abs(extent - nearest) / nearest,
            "width_edge": min(_pow2_edge(int(f), WIDTH_MIN) for f in fullest)}


def sweep(cfg: dict, gen, seed: int) -> dict:
    """{"margins": floats, "work": what every seed has to give alike,
    "moves": what the seed may move, with its distance from an edge}."""
    p = cfg["params"]
    data = gen.generate(cfg["data"], seed)
    k, s = int(p["kmer_size"]), int(p["sketch_size"])
    want = rg.compare_greedy(data.bottom, data.scaled, data.n_kmers, p)
    rows = want["rows"]
    own = data.labels[rows["q"]] == data.labels[rows["r"]]
    gaps = [_cut_gap(refs.mash_matrix([data.bottom[g] for g in group], s, k), 1.0 - p["P_ani"])
            for group in rg.connected_groups(data.bottom, s) if len(group) > 1]
    margins = {
        "ani_gap_own": float(rows["ani"][own].min() - p["S_ani"]),
        "ani_gap_other": float(p["S_ani"] - rows["ani"][~own].max()),
        "coverage_gap": float(min(rows["cov_q"].min(), rows["cov_r"].min()) - p["cov_thresh"]),
        "primary_cut_gap": min(gaps),
        "primary_wrong": ref.partition_mismatch(ref.partition_of(want["primary"]),
                                                ref.partition_of(data.primary_labels)),
        "secondary_wrong": ref.partition_mismatch(ref.partition_of(want["secondary"]),
                                                  ref.partition_of(data.labels)),
    }
    margins["nearest_decision"] = min(margins["ani_gap_own"], margins["ani_gap_other"])
    engine, small, moves = [], {}, {"extent": [], "extent_edge": [], "width_edge": [], "pack_edge": []}
    by_size: dict[int, int] = {}
    for label in np.unique(want["primary"]):
        group = np.flatnonzero(want["primary"] == label)
        m = len(group)
        if m == 1:
            continue
        consumed = int(np.isin(rows["q"], group).sum())
        if m <= ENGINE_OVER:
            extent = len(np.unique(np.concatenate([data.scaled[g] for g in group])))
            by_size[m] = max(by_size.get(m, 0), extent)
            moves["pack_edge"].append(_pow2_edge(extent, PACK_MIN))
            for key, value in (("clusters", 1), ("rows", m), ("compared_pairs", consumed)):
                small[key] = small.get(key, 0) + value
            continue
        visited = group[rg.visiting_order(data.n_kmers[group])]
        founds = np.zeros(m, bool)  # the first visited of a secondary cluster founds it
        founds[np.unique(want["secondary"][visited], return_index=True)[1]] = True
        geometry = engine_geometry([data.scaled[g] for g in group])
        engine.append({"rows": m, "blocks": -(-m // BLOCK), "reps": int(founds.sum()),
                       "rep_rows_real": int(sum(founds[:b0].sum() for b0 in range(0, m, BLOCK))),
                       "compared_pairs": consumed, "chunks": geometry["chunks"],
                       "widths": geometry["widths"]})
        for key in ("extent", "extent_edge", "width_edge"):
            moves[key].append(geometry[key])
    work = {"engine": engine, "batched": small,
            "pack_buckets": {m: _pow2(e, PACK_MIN) for m, e in sorted(by_size.items())},
            "ndb_rows": len(rows["q"]), "cdb_rows": len(data.names),
            "secondary_clusters": len(np.unique(want["secondary"]))}
    return {"margins": margins, "work": work, "moves": moves}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="0-15")
    ap.add_argument("--rehearse", action="store_true", help="the configuration's toy sizes")
    args = ap.parse_args(argv)
    cfg = cells.read_json(os.path.join(BENCH_DIR, "configs", args.config + ".json"))
    if args.rehearse:
        cfg["data"].update(cfg.get("rehearse", {}))
    gen = cells.load_module(os.path.join(BENCH_DIR, "generators", cfg["generator"] + ".py"))
    lo, _, hi = args.seeds.partition("-")
    seeds = list(range(int(lo), int(hi or lo) + 1))
    found = []
    for seed in seeds:
        found.append(sweep(cfg, gen, seed))
        moves = found[-1]["moves"]
        print(f"seed {seed}: " + " ".join(f"{k}={v:.5g}" for k, v in found[-1]["margins"].items())
              + f" extent={moves['extent']} " + " ".join(
                  f"{k}={min(moves[k]):.4f}" for k in ("extent_edge", "width_edge", "pack_edge")),
              flush=True)
    print(f"work, seed {seeds[0]}: {found[0]['work']}", flush=True)
    differ = [seed for seed, f in zip(seeds, found) if f["work"] != found[0]["work"]]
    margins = [f["margins"] for f in found]
    extents = np.array([f["moves"]["extent"] for f in found])
    print(f"{args.config}, seeds {args.seeds}: work differs from seed {seeds[0]}'s on seeds: "
          f"{differ or 'none'} | smallest over the seeds: " +
          " ".join(f"{k}={min(m[k] for m in margins):.5g}" for k in margins[0] if "wrong" not in k) +
          " " + " ".join(f"{k}={min(min(f['moves'][k]) for f in found):.4f}"
                         for k in ("extent_edge", "width_edge", "pack_edge")) +
          " | largest: " + " ".join(f"{k}={max(m[k] for m in margins)}" for k in margins[0] if "wrong" in k) +
          f" | extent moves by at most {int((extents.max(axis=0) - extents.min(axis=0)).max())} ids "
          f"({extents.min(axis=0).tolist()} to {extents.max(axis=0).tolist()})", flush=True)
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())

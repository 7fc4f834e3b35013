#!/usr/bin/env python3
"""The reference alone over several seeds, for a configuration planted by
``generators/planted_fasta.py``: how far every quantity that is compared
with a threshold lies from it, and whether the reference's own clusterings
are the planted ones. Run on the CPU before any chip time is spent; PERF.md
quotes the output. Each seed writes the collection (a gigabyte at full size)
under a temporary directory and removes it.

    python3 benchmark/margin_sweep_fasta.py --config mag_fasta_384 --seeds 0-7 [--rehearse]

For each seed: the ANI gaps (the least ANI inside a planted group over S_ani,
S_ani over the largest ANI between two groups of one root), the gap of either
average-linkage tree to its cut, the least distance of a coverage to
cov_thresh, of a completeness, a contamination and a length to their filters,
the least gap between the two best scores of a cluster (and the exact ties),
and the genomes off the planted partitions. No program code runs.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import tempfile

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark import cells, fasta_jobs  # noqa: E402
from benchmark import reference_fasta as rf  # noqa: E402
from benchmark.margin_sweep_species import _cut_gap  # noqa: E402


def sweep(cfg: dict, gen, seed: int) -> dict:
    p = cfg["params"]
    out_dir = tempfile.mkdtemp(prefix="margin_fasta_")
    try:
        data = gen.prepare(cfg, seed, out_dir)["data"]
        sketches, _, want = fasta_jobs.reference_answers(data, p)
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    where = {g: i for i, g in enumerate(data.names)}
    idx = np.array([where[g] for g in want["kept"]])
    root, group = data.primary_labels[idx], data.labels[idx]
    same_group = group[:, None] == group[None, :]
    same_root = root[:, None] == root[None, :]
    off = ~np.eye(len(idx), dtype=bool)
    ani, cov = want["ani"], want["cov"]
    compared = ~np.isnan(ani) & off
    inside, across = compared & same_group, compared & ~same_group
    # the secondary trees: the merge nearest the cut over every primary cluster
    sec_gap = np.inf
    labels = np.array([want["primary"][g] for g in want["kept"]])
    for label in np.unique(labels):
        members = np.flatnonzero(labels == label)
        if len(members) > 1:
            a, c = ani[np.ix_(members, members)], cov[np.ix_(members, members)]
            dist = 1.0 - np.where((c >= p["cov_thresh"]) & (c.T >= p["cov_thresh"]), a, 0.0)
            np.fill_diagonal(dist, 0.0)
            sec_gap = min(sec_gap, _cut_gap(dist, 1.0 - p["S_ani"]))
    lengths = np.array([s["length"] for s in sketches])
    gap, ties = rf.score_gaps(want)
    planted = {level: {g: int(lab[i]) for g, i in zip(want["kept"], idx)}
               for level, lab in (("primary", data.primary_labels), ("secondary", data.labels))}
    return {
        "files_gb": float(data.bases.sum()) * (1 + 1 / cfg["data"]["line_width"]) / 1e9,
        "kept": len(idx), "multi_group_roots": int(sum(
            len(set(group[root == r])) > 1 for r in np.unique(root))),
        "scaled_min": min(len(sketches[i]["scaled"]) for i in idx),
        "scaled_max": max(len(sketches[i]["scaled"]) for i in idx),
        "ani_gap_inside": float(ani[inside].min() - p["S_ani"]) if inside.any() else np.inf,
        "ani_gap_across": float(p["S_ani"] - ani[across].max()) if across.any() else np.inf,
        "secondary_cut_gap": float(sec_gap),
        "mash_largest_in_root": float(want["dist"][same_root & off].max()) if (same_root & off).any() else 0.0,
        "mash_least_across_roots": float(want["dist"][~same_root].min()),
        "primary_cut_gap": _cut_gap(want["dist"], 1.0 - p["P_ani"]),
        "coverage_gap": float(np.abs(cov[compared] - p["cov_thresh"]).min()),
        "coverage_under": int((cov[compared] < p["cov_thresh"]).sum()),
        "completeness_gap": float(np.abs(data.completeness - p["completeness"]).min()),
        "contamination_gap": float(np.abs(data.contamination - p["contamination"]).min()),
        "length_gap": float(np.abs(lengths - p["length"]).min()),
        "score_gap": gap, "score_ties": ties,
        "primary_wrong": rf.partition_mismatch(want["primary"], planted["primary"]),
        "secondary_wrong": rf.partition_mismatch(want["secondary"], planted["secondary"]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="0-7")
    ap.add_argument("--rehearse", action="store_true", help="the configuration's toy sizes")
    args = ap.parse_args(argv)
    cfg = cells.read_json(os.path.join(BENCH_DIR, "configs", args.config + ".json"))
    if args.rehearse:
        cfg["data"].update(cfg.get("rehearse", {}))
    gen = cells.load_module(os.path.join(BENCH_DIR, "generators", cfg["generator"] + ".py"))
    lo, _, hi = args.seeds.partition("-")
    rows = []
    for seed in range(int(lo), int(hi or lo) + 1):
        rows.append(sweep(cfg, gen, seed))
        print(f"seed {seed}: " + " ".join(f"{k}={v:.5g}" for k, v in rows[-1].items()), flush=True)
    least = [k for k in rows[0] if "gap" in k or k in ("kept", "scaled_min", "mash_least_across_roots")]
    most = [k for k in rows[0] if "wrong" in k or k in ("kept", "scaled_max", "mash_largest_in_root",
                                                         "coverage_under", "score_ties", "files_gb")]
    print(f"{args.config}, seeds {args.seeds}: smallest over the seeds: "
          + " ".join(f"{k}={min(r[k] for r in rows):.5g}" for k in least)
          + " | largest: " + " ".join(f"{k}={max(r[k] for r in rows):.5g}" for k in most), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

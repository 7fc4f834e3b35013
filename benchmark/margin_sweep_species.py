#!/usr/bin/env python3
"""The reference alone over many seeds, for a configuration planted by
``generators/planted_species.py``: how far every quantity that is compared
with a threshold lies from it, and what the planting gives (group sizes, the
vocabulary). Run on the CPU before any chip time is spent; PERF.md quotes
the output.

    python3 benchmark/margin_sweep_species.py --config ecoli_1k --seeds 0-31 [--rehearse]

For each seed: the ANI gaps of pairs (the least ANI inside a group over
S_ani, S_ani over the largest ANI across groups), the gaps of the two
average-linkage trees to their cuts (the merge height nearest the cut, on
either side), the largest Mash distance, and whether the reference's own
clusterings are the planted ones. No program code runs.
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
from benchmark import reference_species as refs  # noqa: E402


def _cut_gap(dist: np.ndarray, cutoff: float) -> float:
    """Distance from `cutoff` to the nearest merge height of the average-linkage tree."""
    import scipy.cluster.hierarchy as sch
    import scipy.spatial.distance as ssd

    heights = sch.linkage(ssd.squareform(dist, checks=False), method="average")[:, 2]
    return float(np.min(np.abs(heights - cutoff)))


def sweep(cfg: dict, gen, seed: int) -> dict:
    p = cfg["params"]
    data = gen.generate(cfg["data"], seed)
    k, s = int(p["kmer_size"]), int(p["sketch_size"])
    n = len(data.names)
    dist = refs.mash_matrix(data.bottom, s, k)
    primary = refs.primary_labels(dist, 1.0 - p["P_ani"])
    ani, cov, labels = refs.secondary_of_cluster(data.scaled, k, p["S_ani"], p["cov_thresh"])
    same = data.labels[:, None] == data.labels[None, :]
    off = ~np.eye(n, dtype=bool)
    gated = np.where((cov >= p["cov_thresh"]) & (cov.T >= p["cov_thresh"]), ani, 0.0)
    sec_dist = 1.0 - gated
    np.fill_diagonal(sec_dist, 0.0)
    sizes = np.bincount(data.labels)
    return {
        "largest_group_share": sizes.max() / n, "smallest_group": int(sizes.min()),
        "vocabulary": len(np.unique(np.concatenate(data.scaled))),
        "ani_gap_inside": float(ani[same & off].min() - p["S_ani"]),
        "ani_gap_across": float(p["S_ani"] - ani[~same].max()),
        "secondary_cut_gap": _cut_gap(sec_dist, 1.0 - p["S_ani"]),
        "mash_largest": float(dist.max()),
        "primary_cut_gap": _cut_gap(dist, 1.0 - p["P_ani"]),
        "coverage_gap": float(cov[off].min() - p["cov_thresh"]),
        "primary_wrong": ref.partition_mismatch(ref.partition_of(primary),
                                                ref.partition_of(data.primary_labels)),
        "secondary_wrong": ref.partition_mismatch(ref.partition_of(labels),
                                                  ref.partition_of(data.labels)),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="0-31")
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
    print(f"{args.config}, seeds {args.seeds}: smallest over the seeds: " +
          " ".join(f"{k}={min(r[k] for r in rows):.5g}" for k in rows[0]
                   if "gap" in k or k in ("vocabulary", "smallest_group", "largest_group_share")) +
          " | largest: " + " ".join(f"{k}={max(r[k] for r in rows):.5g}" for k in rows[0]
                                    if "wrong" in k or k in ("vocabulary", "mash_largest",
                                                             "largest_group_share")),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The reference alone over many seeds: how far every quantity that is
compared with a threshold lies from it. Run on the CPU before any chip time
is spent; PERF.md quotes the output.

    python3 benchmark/margin_sweep.py --config mags_5k --seeds 0-31 [--rehearse]

For each seed, the smallest gap between a compared quantity and its threshold
(Mash distance against 1 - P_ani, ANI against S_ani, the retention bound), and
whether the reference's own clustering is the planted one. No program code
runs: the planting is widened here until the gaps are comfortable.
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


def sweep_sketches(cfg: dict, gen, seed: int) -> dict:
    p = cfg["params"]
    data = gen.generate(cfg["data"], seed)
    k, s = int(p["kmer_size"]), int(p["sketch_size"])
    edges = ref.mash_edges(data.bottom, s, k)
    cut = 1.0 - p["P_ani"]
    same = [d for (i, j), d in edges.items() if data.labels[i] == data.labels[j]]
    cross = [d for (i, j), d in edges.items() if data.labels[i] != data.labels[j]]
    part = set(ref.primary_partition(len(data.names), edges, cut))
    out = {"clusters": len(part),
           "primary_wrong": ref.partition_mismatch(part, ref.partition_of(data.labels)),
           "mash_gap_inside": cut - max(same, default=0.0),
           "mash_gap_across": min(cross, default=1.0) - cut,
           "retention_gap": min((abs(d - p["retention_dist"]) for d in edges.values()), default=1.0)}
    multi = sorted((sorted(c) for c in part if len(c) > 1), key=len, reverse=True)[:40]
    anis, wrong = [], 0
    for group in multi:
        ani, _, labels = ref.secondary_of_cluster([data.scaled[g] for g in group], k,
                                                  p["S_ani"], p["cov_thresh"])
        anis.append(ani[np.triu_indices(len(group), 1)].min())
        wrong += len(set(labels)) - 1
    out["ani_gap_inside"] = (min(anis) - p["S_ani"]) if anis else 1.0
    out["secondary_wrong"] = wrong
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="0-31")
    ap.add_argument("--rehearse", action="store_true", help="the configuration's toy sizes")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="override one numeric key of the configuration's data (to try a planting)")
    args = ap.parse_args(argv)
    cfg = cells.read_json(os.path.join(BENCH_DIR, "configs", args.config + ".json"))
    if args.rehearse:
        cfg["data"].update(cfg.get("rehearse", {}))
    for kv in args.set:
        key, value = kv.split("=")
        cfg["data"][key] = type(cfg["data"][key])(float(value))
    gen = cells.load_module(os.path.join(BENCH_DIR, "generators", cfg["generator"] + ".py"))
    lo, _, hi = args.seeds.partition("-")
    rows = []
    for seed in range(int(lo), int(hi or lo) + 1):
        row = sweep_sketches(cfg, gen, seed)
        rows.append(row)
        print(f"seed {seed}: " + " ".join(f"{k}={v:.5g}" for k, v in row.items()), flush=True)
    print(f"{args.config}, seeds {args.seeds}: smallest over the seeds: " +
          " ".join(f"{k}={min(r[k] for r in rows):.5g}" for k in rows[0] if "gap" in k) +
          " | largest: " + " ".join(f"{k}={max(r[k] for r in rows):.5g}" for k in rows[0] if "wrong" in k),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

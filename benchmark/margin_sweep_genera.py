#!/usr/bin/env python3
"""The reference and the geometry alone over many seeds, at full size, for a
configuration planted by ``generators/planted_genera.py``: everything
``margin_sweep_release.py`` prints (the margins of the greedy answer, the work
that has to be equal over the seeds entry for entry, what the seed may move),
and what the chains add. Run on the CPU before any chip time is spent;
``README_gtdb_genera_6k.md`` and PERF.md quote the output.

    python3 benchmark/margin_sweep_genera.py --config gtdb_genera_6k --seeds 0-15 [--rehearse]

For each seed, from ``reference_greedy`` on exact Mash distances (no program
code runs):

- `primary_cut_gap`: the least distance of a merge height of the full-matrix
  average linkage from the cutoff, over every connected group (a genus is
  one); `primary_wrong`: genomes whose reference cluster is not the planted
  one;
- for every link of every chain the cross pairs' mean distance and how many
  lie at or under ``BRIDGE_AT`` (three standard deviations of a bottom-1,000
  estimate under the cutoff): `link_mean` (least and largest over the links)
  and `link_bridges` (the least over the links; the issue asks for four);
- `loose`: components of the graph of pairs at or under the cutoff that are
  not cliques, and `rows_loose`, the genomes in them: what the program's
  record has to read under `primary_linkage`; `bridge_share`: of the pairs at
  or under the cutoff, the share that joins two planted clusters;
- `mdb_rows`: pairs at or under the retention bound (what the streaming
  primary keeps and `Mdb.csv` holds), `mdb_noncluster`: the share of them that
  joins two different primary clusters; `steps`: for pairs 1, 2, 3, ... steps
  apart along a chain, their mean distance under 1 and the share retained.
  These move with the seed (a pair three steps apart shares a handful of 1,000
  hashes and falls on either side of the bound): the last line gives the
  spread of `mdb_rows` over the seeds;
- `device_calls`: the engine's program calls a job, from the work: a call a
  vocabulary chunk a representative tile and one for the block itself
  (``cluster/greedy.py``), summed over the blocks of every engine cluster.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark import cells  # noqa: E402
from benchmark import margin_sweep_release as msr  # noqa: E402
from benchmark import reference_genera as rgen  # noqa: E402
from benchmark import reference_greedy as rg  # noqa: E402

BRIDGE_AT = 0.085
REP_TILE = 512
EDGES = ("extent_edge", "width_edge", "pack_edge")
# The table is laid out for the program's present shape buckets (a chunk of
# 262,144 ids, widths and packs rounded to powers of two: margin_sweep_release):
# the work is equal over the seeds only while every size stays clear of a
# bucket's edge, so a size within this share of one fails the sweep. A change
# of the bucketing shows here, before it shows as a compile inside a window.
EDGE_LEAST = 0.02


def chain_geometry(data, mash: dict, params: dict) -> dict:
    """What the chains give, from the reference's exact pairs under 1."""
    n, cutoff, keep = len(data.names), 1.0 - params["P_ani"], params["retention_dist"]
    i, j, d = mash["i"], mash["j"], mash["dist"]
    ci, cj = data.primary_labels[i], data.primary_labels[j]
    under, kept = d <= cutoff, d <= keep
    loose, rows_loose = rgen.loose_components(n, i[under], j[under])
    # a genus lays its clusters out in chain order, numbered one after the other
    apart = np.where(data.genus[i] == data.genus[j], np.abs(ci - cj), -1)
    size = np.bincount(data.primary_labels)
    first = np.minimum(ci, cj)
    link_mean, link_bridges = [], []
    for a, b in data.links:
        across = (apart == 1) & (first == a)
        # a cross pair that shares no hash is at 1 and not among the pairs under 1
        link_mean.append((d[across].sum() + size[a] * size[b] - across.sum()) / (size[a] * size[b]))
        link_bridges.append(int((d[across] <= BRIDGE_AT).sum()))
    steps = {}
    for step in range(1, int(apart.max()) + 1):
        at = apart == step
        steps[step] = (float(d[at].mean()), float(kept[at].mean()), int(at.sum()))
    return {
        "loose": loose, "rows_loose": rows_loose,
        "bridge_share": float((under & (ci != cj)).sum() / max(under.sum(), 1)),
        "link_mean": (min(link_mean), max(link_mean)), "link_bridges": min(link_bridges),
        "mdb_rows": int(kept.sum()),
        "mdb_noncluster": float((kept & (ci != cj)).sum() / max(kept.sum(), 1)),
        "steps": steps,
    }


def device_calls(engine: list[dict]) -> int:
    """Program calls of the greedy engine for the clusters of `work.engine`:
    each block is compared with every representative tile, chunk by chunk,
    and with itself. The representatives before a block are not in the work's
    summary, so a cluster of at most ``REP_TILE`` representatives is counted
    (every cluster of the table); a larger one raises."""
    if any(e["reps"] > REP_TILE for e in engine):
        raise ValueError("a cluster over one representative tile: count its tiles block by block")
    return sum(e["blocks"] * 2 * e["chunks"] for e in engine)


def sweep(cfg: dict, gen, seed: int) -> dict:
    """``margin_sweep_release.sweep``'s {"margins", "work", "moves"} plus
    "chains" (``chain_geometry``); the work gains `device_calls`."""
    found = msr.sweep(cfg, gen, seed)
    p = cfg["params"]
    data = gen.generate(cfg["data"], seed)
    _, mash = rg.primary(data.bottom, int(p["sketch_size"]), int(p["kmer_size"]), 1.0 - p["P_ani"])
    found["chains"] = chain_geometry(data, mash, p)
    # the reference numbers its clusters by its own tree, which the seed moves: one order
    found["work"]["engine"] = sorted(found["work"]["engine"], key=lambda e: sorted(e.items(), key=str))
    found["work"]["device_calls"] = device_calls(found["work"]["engine"])
    found["work"]["loose"] = (found["chains"]["loose"], found["chains"]["rows_loose"])
    return found


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
        f = found[-1]
        chains, moves = f["chains"], f["moves"]
        print(f"seed {seed}: " + " ".join(f"{k}={v:.5g}" for k, v in f["margins"].items())
              + f" loose={chains['loose']} rows_loose={chains['rows_loose']}"
              + f" link_bridges={chains['link_bridges']}"
              + f" link_mean={chains['link_mean'][0]:.4f}-{chains['link_mean'][1]:.4f}"
              + f" bridge_share={chains['bridge_share']:.5f} mdb_rows={chains['mdb_rows']}"
              + f" mdb_noncluster={chains['mdb_noncluster']:.4f} steps="
              + ",".join(f"{s}:{m:.3f}/{r:.2f}" for s, (m, r, _) in chains["steps"].items())
              + f" extent={moves['extent']} " + " ".join(f"{k}={min(moves[k]):.4f}" for k in EDGES),
              flush=True)
    print(f"work, seed {seeds[0]}: {found[0]['work']}", flush=True)
    differ = [seed for seed, f in zip(seeds, found) if f["work"] != found[0]["work"]]
    margins = [f["margins"] for f in found]
    rows = np.array([f["chains"]["mdb_rows"] for f in found])
    spread = float((rows.max() - rows.min()) / np.median(rows))
    few = [seed for seed, f in zip(seeds, found) if f["chains"]["link_bridges"] < 4]
    wrong = [seed for seed, m in zip(seeds, margins) if m["primary_wrong"] or m["secondary_wrong"]]
    near = [seed for seed, m in zip(seeds, margins) if m["primary_cut_gap"] < 0.005]
    # the toy table is laid out for no bucket: its edges are printed and fail nothing
    edgy = [seed for seed, f in zip(seeds, found)
            if not args.rehearse and min(min(f["moves"][k]) for k in EDGES) < EDGE_LEAST]
    print(f"{args.config}, seeds {args.seeds}: work differs from seed {seeds[0]}'s on seeds: "
          f"{differ or 'none'} | off the planted partitions on seeds: {wrong or 'none'} | a merge "
          f"within 0.005 of the cutoff on seeds: {near or 'none'} | a link with under four pairs at "
          f"or under {BRIDGE_AT} on seeds: {few or 'none'} | a size within {EDGE_LEAST:.0%} of a shape "
          f"bucket's edge on seeds: {edgy or 'none'} | smallest over the seeds: " +
          " ".join(f"{k}={min(m[k] for m in margins):.5g}" for k in margins[0] if "wrong" not in k) +
          f" link_bridges={min(f['chains']['link_bridges'] for f in found)} " +
          " ".join(f"{k}={min(min(f['moves'][k]) for f in found):.4f}" for k in EDGES) +
          f" | mdb_rows {rows.min()} to {rows.max()}, spread {100 * spread:.2f}% of the median",
          flush=True)
    return 1 if differ or wrong or near or few or edgy or spread >= 0.02 else 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The reference and the geometry alone over many seeds, at full size, for a
configuration planted by ``generators/planted_index.py``: that every seed
gives an `index update` the same WORK, and how far every quantity that is
compared with a threshold or rounded to a shape lies from its edge. Run on the
CPU before any chip time is spent; PERF.md quotes the output.

    python3 benchmark/margin_sweep_index.py --config gtdb_index_6k --seeds 0-15 [--rehearse]

For each seed, from ``reference_index`` (no program code runs):

- margins of the answer: the primary trees' merge nearest the cut, the
  secondary trees' merge nearest 1 - S_ani over every cluster of the union,
  the least coverage of a pair inside a secondary cluster over cov_thresh,
  the genomes off the planted partitions (union, and the index alone), the
  least gap between the two best scores of a secondary cluster whose two best
  differ, and the clusters whose two best tie exactly;
- the work, which has to be equal over the seeds entry for entry (`work`):
  the union clusters whose member set changed, their members, the secondary
  calls and the singletons scored (``reference_index.expected_work``), the
  rectangle's tiles and pairs, the new edges inside the retention bound, and
  for each changed cluster of two genomes or more the shape its per-cluster
  secondary call takes: its row bucket, and the power-of-two bucket of its
  vocabulary, or beyond the one-shot budget its chunks and their id width
  (``engines.containment_matrices`` -> ``matmul_chunked``, rules copied below);
- what the seed may move, and how far from an edge it stays: each changed
  cluster's vocabulary against the nearest power of two (`pack_edge`), a
  chunked cluster's extent against the nearest multiple of its chunk
  (`extent_edge`) and its fullest chunk row against the nearest power of two
  (`width_edge`).

The program's rules the geometry copies (ops/containment.py): rows pad to a
power of two of at least 64; a vocabulary pads to a power of two of at least
8,192; a call is one-shot while rows_pad x (v_pad + 1) <= 2^29; beyond it the
vocabulary is cut in chunks of the widest power of two with rows_pad x (chunk
+ 1) <= 2^29, or of 32,768 ids shipped as uint16 where that plan is fewer
bytes, each chunk's id width the power of two at or over the fullest row of
any chunk, at least 128.
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
from benchmark import reference_index as ri  # noqa: E402
from benchmark import reference_species as refs  # noqa: E402
from benchmark.margin_sweep_release import _pow2, _pow2_edge  # noqa: E402
from benchmark.margin_sweep_species import _cut_gap  # noqa: E402

BUDGET = 1 << 29
ROWS_MIN, VOCAB_MIN, WIDTH_MIN, U16_CHUNK = 64, 8_192, 128, 1 << 15
N50 = 50_000


def _chunk_plan(vocab: np.ndarray, scaled: list[np.ndarray], v_chunk: int) -> tuple[int, int, int]:
    """(chunks, id width, fullest row of any chunk) of a cluster cut at `v_chunk`."""
    chunks = max(1, -(-len(vocab) // v_chunk))
    fullest = max(int(np.bincount(np.searchsorted(vocab, s) // v_chunk, minlength=chunks).max())
                  for s in scaled)
    return chunks, _pow2(fullest, WIDTH_MIN), fullest


def call_shape(scaled: list[np.ndarray]) -> dict:
    """The shape of one cluster's per-cluster secondary call."""
    vocab = np.unique(np.concatenate(scaled))
    extent, rows_pad = len(vocab), _pow2(len(scaled), ROWS_MIN)
    v_pad = _pow2(extent, VOCAB_MIN)
    out = {"rows_pad": rows_pad, "pack_edge": _pow2_edge(extent, VOCAB_MIN), "extent": extent}
    if rows_pad * (v_pad + 1) <= BUDGET:
        return {**out, "route": "one_shot", "v_pad": v_pad}
    fit = BUDGET // rows_pad - 1
    v_chunk = max(VOCAB_MIN, 1 << (fit.bit_length() - 1))
    plan = _chunk_plan(vocab, scaled, v_chunk)
    if v_chunk > U16_CHUNK:
        plan16 = _chunk_plan(vocab, scaled, U16_CHUNK)
        if plan16[0] * plan16[1] * 2 < plan[0] * plan[1] * 4:
            v_chunk, plan = U16_CHUNK, plan16
    nearest = max(1, round(extent / v_chunk)) * v_chunk
    return {**out, "route": "matmul_chunked", "v_chunk": v_chunk, "chunks": plan[0], "width": plan[1],
            "extent_edge": abs(extent - nearest) / nearest, "width_edge": _pow2_edge(plan[2], WIDTH_MIN)}


def sweep(cfg: dict, gen, seed: int) -> dict:
    p = cfg["params"]
    data = gen.generate(cfg["data"], seed)
    u, n_old = data.union, data.n_old
    k, s = int(p["kmer_size"]), int(p["sketch_size"])
    n50 = np.full(len(u.names), N50, np.int64)
    want = ri.from_scratch(u.bottom, u.scaled, u.names, u.length, n50, p)
    old_primary = rg.primary(u.bottom[:n_old], s, k, 1.0 - p["P_ani"])[0]
    gaps = [_cut_gap(refs.mash_matrix([u.bottom[g] for g in group], s, k), 1.0 - p["P_ani"])
            for group in rg.connected_groups(u.bottom, s) if len(group) > 1]
    pairs = want["pairs"]
    same = want["secondary"][pairs["q"]] == want["secondary"][pairs["r"]]
    best2: dict = {}
    for g in np.argsort(-want["score"], kind="stable"):
        best2.setdefault(int(want["secondary"][g]), []).append(float(want["score"][g]))
    score_gaps = [v[0] - v[1] for v in best2.values() if len(v) > 1]
    margins = {
        "primary_cut_gap": min(gaps),
        "ani_gap_own": float(pairs["ani"][same].min() - p["S_ani"]) if same.any() else np.inf,
        "ani_gap_other": float(p["S_ani"] - pairs["ani"][~same].max()) if (~same).any() else np.inf,
        "coverage_gap": float(pairs["cov"][same].min() - p["cov_thresh"]) if same.any() else np.inf,
        "score_gap": min([g for g in score_gaps if g > 0], default=np.inf),
        "score_ties": sum(g == 0 for g in score_gaps),
        "primary_wrong": ref.partition_mismatch(ref.partition_of(want["primary"]),
                                                ref.partition_of(u.primary_labels)),
        "secondary_wrong": ref.partition_mismatch(ref.partition_of(want["secondary"]),
                                                  ref.partition_of(u.labels)),
        "old_primary_wrong": ref.partition_mismatch(ref.partition_of(old_primary),
                                                    ref.partition_of(u.primary_labels[:n_old])),
    }
    shapes: dict[tuple, int] = {}
    moves = {"pack_edge": [], "extent_edge": [], "width_edge": [], "extent": []}
    for members in ri.changed_clusters(want["primary"], old_primary):
        if len(members) < 2:
            continue
        shape = call_shape([u.scaled[g] for g in members])
        moves["pack_edge"].append(shape["pack_edge"])
        if shape["route"] == "matmul_chunked":
            key = ("matmul_chunked", shape["rows_pad"], shape["v_chunk"], shape["chunks"], shape["width"])
            moves["extent_edge"].append(shape["extent_edge"])
            moves["width_edge"].append(shape["width_edge"])
            moves["extent"].append(shape["extent"])
        else:
            key = ("one_shot", shape["rows_pad"], shape["v_pad"])
        shapes[key] = shapes.get(key, 0) + 1
    m = want["mash"]
    reach = (m["j"] >= n_old) & (m["dist"] <= p["retention_dist"])
    block = int(p["streaming_block"])
    n = len(u.names)
    row_blocks = -(-n // block)
    tiles = sum(row_blocks - max(bi, n_old // block) for bi in range(row_blocks))
    work = {**ri.expected_work(want["primary"], old_primary),
            "new_edges": int(reach.sum()), "tiles": tiles,
            "calls_by_shape": {" ".join(map(str, k)): v for k, v in sorted(shapes.items())},
            "primary_clusters": int(want["primary"].max()),
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
                  f"{k}={min(moves[k], default=np.inf):.4f}" for k in ("pack_edge", "extent_edge", "width_edge")),
              flush=True)
    print(f"work, seed {seeds[0]}: {found[0]['work']}", flush=True)
    differ = [seed for seed, f in zip(seeds, found) if f["work"] != found[0]["work"]]
    for seed, f in zip(seeds, found):
        if seed in differ:
            print(f"work, seed {seed}: {f['work']}", flush=True)
    margins = [f["margins"] for f in found]
    counted = ("wrong", "ties")
    print(f"{args.config}, seeds {args.seeds}: work differs from seed {seeds[0]}'s on seeds: "
          f"{differ or 'none'} | smallest over the seeds: " +
          " ".join(f"{k}={min(m[k] for m in margins):.5g}" for k in margins[0]
                   if not any(c in k for c in counted)) +
          " " + " ".join(f"{k}={min(min(f['moves'][k], default=np.inf) for f in found):.4f}"
                         for k in ("pack_edge", "extent_edge", "width_edge")) +
          " | largest: " + " ".join(f"{k}={max(m[k] for m in margins)}" for k in margins[0]
                                    if any(c in k for c in counted)), flush=True)
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())

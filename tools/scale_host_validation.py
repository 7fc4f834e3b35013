"""50k/100k-genome HOST-path validation on CPU (no TPU required).

Usage:  JAX_PLATFORMS=cpu python tools/scale_host_validation.py [N]
            [--greedy] [--hard]

The tile compute (the TPU part) is skipped by forging the streaming
row-block shard checkpoints from exact numpy union-bottom-s distances.
The real pipeline then runs end to end: shard resume at scale, native
sparse UPGMA, batched secondary containment (real CPU compute), Cdb
assembly, and a full resume — with wall/RSS recorded.

Two planting modes:

- default (the round-3 rows): contiguous clusters of <= 20 genomes, so
  every within-cluster pair lies in a 19-wide index window and every
  cross-pair is distance ~1 (independent 63-bit draws; 3+ shared hashes
  of 1000 are needed to clear the 0.25 retention bound).
- ``--hard`` (VERDICT r3 weak #4 — the friendlier-than-reality fix):
  heavy-tailed zipf cluster sizes straddling the SMALL_CLUSTER_MAX=32
  batching boundary (capped at 64), ONE ~5k-genome cluster, and a random
  permutation of genome order, so shard content comes from anywhere in
  the row blocks and the big-cluster secondary path runs. The big
  cluster is constructed analytically exact: every member holds the same
  bottom-999 pool plus one member-unique hash LARGER than the whole
  pool, so each pair's union-bottom-1000 shares exactly 999 of 1000 —
  all C(5k,2) ~= 12.5M edges carry one identical tiny distance (a
  tie-rich UPGMA stress) with zero per-pair set math. ``--hard`` implies
  the greedy combo: the 5k cluster rides the per-cluster greedy route
  (its real-compute cost on one CPU core is bounded), exactly the
  north-star configuration.
"""

import json
import logging
import os
import resource
import sys
import tempfile
import time

import numpy as np
import pandas as pd

# surface the pipeline's own INFO lines (primary cluster counts, shard
# resume counts, per-stage perf) — without a handler the long
# d_cluster_wrapper stretch between "forged" and RESULT is a blind spot
logging.basicConfig(level=logging.INFO, format="%(asctime)s %(levelname)s %(message)s")

# runnable as `python tools/scale_host_validation.py` from anywhere: bench.py
# and the drep_tpu package live at the repo root, one level up
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_argv, sys.argv = sys.argv, ["scale_host_validation"]
import bench as B

sys.argv = _argv
from drep_tpu.cluster.controller import d_cluster_wrapper
from drep_tpu.ingest import DEFAULT_SCALE, GenomeSketches, _save, sketch_args_snapshot
from drep_tpu.ops.merge import cap_merge_tile
from drep_tpu.ops.minhash import mash_distance_from_jaccard, pack_sketches
from drep_tpu.utils.ckptmeta import content_fingerprint, open_checkpoint_dir
from drep_tpu.workdir import WorkDirectory

_pos = [a for a in sys.argv[1:] if not a.startswith("-")]
N = int(_pos[0]) if _pos else 50_000
HARD = "--hard" in sys.argv
# the north-star combo: streaming + greedy (always on under --hard: the 5k
# cluster's all-pairs secondary on one CPU core would measure tile compute
# this tool exists to exclude)
GREEDY = "--greedy" in sys.argv or HARD
K = 21
WINDOW = 19  # max intra-cluster index span (default mode: contiguous, <= 20)
KEEP = 0.25  # max(1 - P_ani, warn_dist) at default flags
BIG = min(5_000, N // 2)  # --hard big-cluster size (capped for small-N smoke runs)
SIZE_CAP = 64  # --hard zipf cap: straddles SMALL_CLUSTER_MAX=32


def plant_hard(n: int, rng: np.random.Generator):
    """Heavy-tailed planted clusters + the analytic 5k cluster; returns
    (GenomeSketches in PLANTED order, cluster sizes in planted order)."""
    s_bottom, s_scaled = 1000, 1200
    sizes = []
    left = n - BIG
    while left > 0:
        m = int(min(rng.zipf(1.7), SIZE_CAP, left))
        sizes.append(m)
        left -= m
    sizes.append(BIG)  # planted LAST: a contiguous span, permuted later
    names, bottoms, scaleds = [], [], []
    gi = 0
    for size in sizes:
        if size == BIG:
            # bottom-999 shared pool from [0, 2^62); per-member unique ODD
            # tag 2^63 + 2m + 1 (top of uint64 range, above int64) —
            # strictly larger than every pool hash, so
            # union-bottom-1000(A_i, A_j) = pool + min(tag_i, tag_j) and
            # every pair shares exactly 999/1000. Everything on this path
            # must stay uint64: an int64 cast would wrap the tags negative
            # and break the sorted-unique sketch contract
            pool = np.unique(rng.integers(0, 2**62, size=1200, dtype=np.uint64))[:999]
            tags = (2**62 + np.arange(size, dtype=np.uint64)) * np.uint64(2) + np.uint64(1)
            c_scaled = np.unique(rng.integers(0, 2**62, size=int(s_scaled * 1.3), dtype=np.uint64))
            for m in range(size):
                bottoms.append(np.sort(np.concatenate([pool, tags[m : m + 1]])))
                keep_s = c_scaled[rng.random(len(c_scaled)) < 0.97]
                own_s = np.unique(rng.integers(0, 2**62, size=s_scaled // 25, dtype=np.uint64))
                scaleds.append(np.sort(np.concatenate([keep_s, own_s])))
                names.append(f"synth_{gi}.fasta")
                gi += 1
        else:
            c_bottom = np.unique(rng.integers(0, 2**63, size=int(s_bottom * 1.6), dtype=np.uint64))
            c_scaled = np.unique(rng.integers(0, 2**63, size=int(s_scaled * 1.3), dtype=np.uint64))
            for _ in range(size):
                keep_b = c_bottom[rng.random(len(c_bottom)) < 0.90]
                own_b = np.unique(rng.integers(0, 2**63, size=s_bottom // 6, dtype=np.uint64))
                bottoms.append(np.sort(np.concatenate([keep_b, own_b]))[:s_bottom])
                keep_s = c_scaled[rng.random(len(c_scaled)) < 0.97]
                own_s = np.unique(rng.integers(0, 2**63, size=s_scaled // 25, dtype=np.uint64))
                scaleds.append(np.sort(np.concatenate([keep_s, own_s])))
                names.append(f"synth_{gi}.fasta")
                gi += 1
    gdb = pd.DataFrame(
        {
            "genome": names,
            "length": np.full(n, 4_000_000, np.int64),
            "N50": np.full(n, 50_000, np.int64),
            "contigs": np.full(n, 100, np.int64),
            "n_kmers": np.full(n, 3_900_000, np.int64),
        }
    )
    return (
        GenomeSketches(
            names=names, gdb=gdb, bottom=bottoms, scaled=scaleds,
            k=K, sketch_size=s_bottom, scale=DEFAULT_SCALE,
        ),
        sizes,
    )


def exact_window_edges(bottoms, windows):
    """Exact union-bottom-s oracle edges: for each (row_lo, row_hi,
    col_hi) window, every pair i in [row_lo, row_hi) x j in (i, col_hi).
    Default mode passes per-row 19-wide windows; --hard passes whole
    cluster spans (row_hi == col_hi)."""
    s = 1000
    ii_l, jj_l, dd_l = [], [], []
    for row_lo, row_hi, col_hi in windows:
        for i in range(row_lo, row_hi):
            a = bottoms[i]
            for j in range(i + 1, col_hi):
                b = bottoms[j]
                inter = np.intersect1d(a, b)
                if len(inter) < 3:  # cannot reach dist <= 0.25 at s=1000
                    continue
                u_t = np.union1d(a, b)[s - 1]
                shared = int((inter <= u_t).sum())
                d = float(mash_distance_from_jaccard(np.float32(shared / s), K, xp=np))
                if d <= KEEP:
                    ii_l.append(i)
                    jj_l.append(j)
                    dd_l.append(d)
    return (
        np.array(ii_l, np.int64),
        np.array(jj_l, np.int64),
        np.array(dd_l, np.float32),
    )


t0 = time.perf_counter()
rng = np.random.default_rng(2)
truth = None
if HARD:
    gs, sizes = plant_hard(N, rng)
    bounds = np.cumsum([0] + sizes)
    truth = np.repeat(np.arange(len(sizes)), sizes)  # planted cluster per genome
else:
    gs = B._plant_sketches(N, rng)
print(f"planted {N} genomes in {time.perf_counter()-t0:.1f}s", flush=True)

t0 = time.perf_counter()
if HARD:
    # exact oracle: windowed pairs for the zipf clusters; the 5k cluster's
    # C(BIG,2) pairs all share exactly 999/1000 by construction
    # the big cluster is ALWAYS planted last — identify it by position,
    # not by value: at small smoke-run N, BIG <= SIZE_CAP and a zipf
    # cluster can tie it
    big_idx = len(sizes) - 1
    assert sizes[big_idx] == BIG
    spans = [
        (int(bounds[c]), int(bounds[c + 1]), int(bounds[c + 1]))
        for c in range(len(sizes))
        if c != big_idx
    ]
    ii, jj, dd = exact_window_edges(gs.bottom, spans)
    big_lo = int(bounds[big_idx])
    bi_i, bi_j = np.triu_indices(BIG, 1)
    d_big = float(mash_distance_from_jaccard(np.float32(999 / 1000), K, xp=np))
    assert d_big <= KEEP
    ii = np.concatenate([ii, bi_i.astype(np.int64) + big_lo])
    jj = np.concatenate([jj, bi_j.astype(np.int64) + big_lo])
    dd = np.concatenate([dd, np.full(len(bi_i), d_big, np.float32)])
    del bi_i, bi_j

    # scatter membership: a random permutation of genome order, with the
    # oracle edges mapped through it (shards then carry edges from
    # anywhere, the real-run shape the contiguous planting never tested)
    perm = rng.permutation(N)  # new index q holds planted genome perm[q]
    pos = np.argsort(perm)  # planted index p now lives at pos[p]
    gs = GenomeSketches(
        names=[f"synth_{q}.fasta" for q in range(N)],  # names follow POSITION
        gdb=gs.gdb.assign(genome=[f"synth_{q}.fasta" for q in range(N)]),
        bottom=[gs.bottom[perm[q]] for q in range(N)],
        scaled=[gs.scaled[perm[q]] for q in range(N)],
        k=gs.k, sketch_size=gs.sketch_size, scale=gs.scale,
    )
    truth = truth[perm]  # truth[q] = planted cluster of the genome at q
    pi, pj = pos[ii], pos[jj]
    ii, jj = np.minimum(pi, pj), np.maximum(pi, pj)
    del pi, pj, pos, perm
    order = np.argsort(ii, kind="stable")
    ii, jj, dd = ii[order], jj[order], dd[order]
    del order
else:
    ii, jj, dd = exact_window_edges(
        gs.bottom, [(i, i + 1, min(i + 1 + WINDOW, N)) for i in range(N)]
    )
print(f"edge oracle: {len(ii)} edges in {time.perf_counter()-t0:.1f}s", flush=True)

packed = pack_sketches(gs.bottom, gs.names, gs.sketch_size)
print("packed", flush=True)

with tempfile.TemporaryDirectory() as td:
    wd = WorkDirectory(td)
    bdb = pd.DataFrame(
        {"genome": gs.names, "location": [f"/nonexistent/{g}" for g in gs.names]}
    )
    _save(wd, gs)
    wd.store_arguments(
        "sketch",
        sketch_args_snapshot(bdb["genome"], K, gs.sketch_size, DEFAULT_SCALE, "splitmix64"),
    )

    # forge the streaming shard checkpoints (exact meta + per-row-block npz)
    # the real path's block rule INCLUDING its small-n clamp
    block = cap_merge_tile(min(1024, max(8, N)), packed.ids.shape[1])
    nt = -(-N // block) * block
    n_blocks = nt // block
    ckpt = wd.get_dir(os.path.join("data", "streaming_primary"))
    meta = {
        "n": N,
        "block": block,
        "k": K,
        "cutoff": round(float(KEEP), 12),
        "sketch_size": int(packed.sketch_size),
        "n_blocks": n_blocks,
        "fingerprint": content_fingerprint(packed.names, packed.counts, packed.ids),
    }
    # first call writes the meta (returns False); a second call must see it
    # as resumable — proving the run's own meta computation will match
    open_checkpoint_dir(ckpt, meta, clear_suffixes=(".npz",))
    assert open_checkpoint_dir(ckpt, meta, clear_suffixes=(".npz",))
    from drep_tpu.utils.ckptmeta import atomic_savez

    blk = ii // block
    for bi in range(n_blocks):
        sel = blk == bi
        atomic_savez(
            os.path.join(ckpt, f"row_{bi:05d}.npz"),
            ii=ii[sel], jj=jj[sel], dist=dd[sel],
        )
    print(f"forged {n_blocks} shards (block={block})", flush=True)

    kw = {"streaming_primary": True}
    if GREEDY:
        kw["greedy_secondary_clustering"] = True
    t0 = time.perf_counter()
    cdb = d_cluster_wrapper(wd, bdb, **kw)
    wall = time.perf_counter() - t0
    # the measurement is only valid if the run RESUMED the forged shards: a
    # meta mismatch silently clears them and recomputes tiles on CPU —
    # reporting tile compute the number claims to exclude
    import glob as _glob

    n_shards_left = len(_glob.glob(os.path.join(ckpt, "row_*.npz")))
    assert n_shards_left == n_blocks, (
        f"forged shards were invalidated ({n_shards_left}/{n_blocks} remain) — "
        "meta drifted from the streaming path; measurement void"
    )
    t0 = time.perf_counter()
    cdb2 = d_cluster_wrapper(wd, bdb, **kw)
    resume_wall = time.perf_counter() - t0
    key = ["genome", "primary_cluster", "secondary_cluster"]

    def _matches_truth(column: str) -> bool:
        # partition equality: distinct (truth, label) combos == distinct
        # truth ids == distinct labels (i.e. a perfect 1:1 relabeling)
        q = cdb["genome"].str.removeprefix("synth_").str.removesuffix(".fasta").astype(int)
        lab = pd.factorize(cdb[column])[0]
        t = truth[q.to_numpy()]
        combos = len(np.unique(np.stack([t, lab]), axis=1).T)
        return bool(combos == len(np.unique(t)) == len(np.unique(lab)))

    out = {
        "n": N,
        "greedy": GREEDY,
        "hard": HARD,
        **(
            {
                "big_cluster": BIG,
                "size_cap": SIZE_CAP,
                "primary_matches_truth": _matches_truth("primary_cluster"),
                "secondary_matches_truth": _matches_truth("secondary_cluster"),
            }
            if HARD
            else {}
        ),
        "edges": int(len(ii)),
        "host_wall_to_cdb_s": round(wall, 1),
        "resume_s": round(resume_wall, 1),
        "primary_clusters": int(cdb["primary_cluster"].max()),
        "secondary_clusters": int(cdb["secondary_cluster"].nunique()),
        "resume_match": bool(
            cdb2.sort_values("genome")[key].reset_index(drop=True).equals(
                cdb.sort_values("genome")[key].reset_index(drop=True)
            )
        ),
        "peak_rss_gb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 2
        ),
    }
    print("RESULT " + json.dumps(out), flush=True)

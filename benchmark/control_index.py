#!/usr/bin/env python3
"""The control of "How `correct` is decided" for a cell of kind `index_jobs`,
at the cell's own size, with that kind's own comparison
(``index_jobs.check_index``) and no program code: two wrong stores put in the
program's place, each of which has to come out as not correct.

    python3 benchmark/control_index.py --workload gtdb_index_6k.update_admit --seeds 1,2 [--rehearse]

- `bfloat16`: the reference computed in the precision below (Mash distances
  and ANIs rounded to bfloat16 before the linkages and the scores see them)
  against the float64 reference: it has to fail both value limits, the new
  edges' distances and the scores.
- `kept`: a sound store but for ONE union cluster whose member set changed
  (the largest), left as generation 0 had it: its old members in their old
  cluster, its joiners clusters of one, the record's counts one cluster short.
  What an update that skips a dirty cluster publishes; it has to fail the
  partitions and the counts.

Not part of a benchmark run; NumPy and SciPy only, so it runs without a chip.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark import cells, check, index_jobs  # noqa: E402
from benchmark import reference_index as ri  # noqa: E402


def answers_of(want: dict, n: int) -> dict:
    """A reference's answers in the form ``index_jobs.read_answers`` gives a
    store's: what a sound program would have left on disk."""
    return {"n": n, "generation": 1, "row": np.arange(n), "primary": want["primary"].copy(),
            "secondary": want["secondary"].copy(), "score": want["score"].copy(),
            "winners": want["winners"].copy(), "winner_clusters": len(np.unique(want["secondary"])),
            "edges": {k: want["mash"][k] for k in ("i", "j", "dist")}}


def keep_generation0(got: dict, work: dict, want: dict, n_old: int) -> tuple[dict, dict]:
    """`got` and the record's counts with the largest changed cluster left as
    generation 0 had it."""
    changed = max(ri.changed_clusters(want["primary"], want["old_primary"]), key=len)
    got = {**got, "primary": got["primary"].copy(), "secondary": got["secondary"].copy()}
    fresh_p, fresh_s = int(got["primary"].max()) + 1, int(got["secondary"].max()) + 1
    for x, g in enumerate(c for c in changed if c >= n_old):  # the joiners: clusters of one
        got["primary"][g], got["secondary"][g] = fresh_p + x, fresh_s + x
    short = {**work, "clusters_recomputed": work["clusters_recomputed"] - 1,
             "members_recomputed": work["members_recomputed"] - len(changed),
             "secondary_calls": work["secondary_calls"] - 1,
             "clusters_reused": work["clusters_reused"] + 1,
             "components_reclustered": work["clusters_recomputed"] - 1}
    return got, short


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    loaded = cells.load_cell(args.workload)
    cfg, mix = loaded["config"], loaded["traffic"]
    if args.rehearse:
        cfg = {**cfg, "data": {**cfg["data"], **cfg.get("rehearse", {})}}
    want_of = [w for w in mix["compare"] if w != "guarantees"]
    all_failed = True
    for seed in (int(s) for s in args.seeds.split(",")):
        data = loaded["generator"].generate(cfg["data"], seed)
        n, p = len(data.names), cfg["params"]
        want = index_jobs.reference_of(data, p)
        want["old_primary"] = ri.rg.primary(data.union.bottom[:data.n_old], int(p["sketch_size"]),
                                            int(p["kmer_size"]), 1.0 - p["P_ani"])[0]
        sound_work = {**want["work"], "components_reclustered": want["work"]["clusters_recomputed"]}
        low = index_jobs.reference_of(data, p, lower_precision=True)
        controls = {
            "bfloat16": (answers_of(low, n), {**low["work"], "components_reclustered":
                                              low["work"]["clusters_recomputed"]}),
            "kept": keep_generation0(answers_of(want, n), sound_work, want, data.n_old),
        }
        for name, (got, work) in controls.items():
            out = index_jobs.check_index(got, work, data, p, want_of, mix["limits"], want)
            print(f"control {name}, {args.workload}, seed {seed}:", flush=True)
            ok = check.report(out)
            values_failed = all(not c["ok"] for c in out if c["limit"] > 0)
            print(f"control {name}, {args.workload}, seed {seed}: correct = {ok}"
                  + (f", every value limit failed = {values_failed}" if name == "bfloat16" else ""),
                  flush=True)
            all_failed = all_failed and not ok and (values_failed or name != "bfloat16")
        sound = index_jobs.check_index(answers_of(want, n), sound_work, data, p, want_of, mix["limits"], want)
        print(f"control, {args.workload}, seed {seed}: the sound answers alone: correct = "
              f"{all(c['ok'] for c in sound)}", flush=True)
        all_failed = all_failed and all(c["ok"] for c in sound)
    return 0 if all_failed else 1


if __name__ == "__main__":
    sys.exit(main())
